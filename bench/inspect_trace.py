"""Print what a profiler trace holds, to read one by hand: its planes and
lines with their event counts, and per device plane the operations that took
most time with the metadata the trace gives them.

    python3 bench/inspect_trace.py <directory or .xplane.pb> [top]
"""
from __future__ import annotations

import sys
from pathlib import Path


def main(argv) -> int:
    from jax.profiler import ProfileData
    path = Path(argv[0])
    top = int(argv[1]) if len(argv) > 1 else 40
    if path.is_dir():
        path = sorted(path.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    print(f"# {path} ({path.stat().st_size} bytes)")
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: " + ", ".join(
            f"{ln.name!r}={sum(1 for _ in ln.events)}" for ln in lines))
        if not plane.name.startswith("/device:"):
            continue
        for ln in lines:
            total, meta = {}, {}
            for e in ln.events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns * 1e-9
                meta.setdefault(e.name, dict(e.stats))
            print(f"  line {ln.name!r}: {sum(total.values()):.6f} s")
            for name, t in sorted(total.items(), key=lambda x: -x[1])[:top]:
                stats = {k: str(v)[:160] for k, v in meta[name].items()}
                print(f"    {t:.6f} s  {name[:100]!r}  {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
