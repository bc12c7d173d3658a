"""The comparison that decides ``correct``: the program's first rounds against
the reference's, from the same weights, tokens and keys.

Four numbers, each with a limit of its own (``bench/limits/<cell>.json``):

  loss_gap    the largest gap, over the first rounds, between the round loss
              the program reports and the reference's, in nats;
  grad_gap    the worst leaf's gap between the norms of the first round's
              change of the weights (the first gradient as the server
              optimizer gets it, times its learning rate), as a share of
              the larger of the reference leaf's norm and the median leaf's;
  change_gap  the same for the change after all the first rounds, over the
              leaves whose first reference gradient is at least a
              thousandth of the median leaf's (a leaf that no step can move
              in bf16, such as a norm scale of 1.0, moves by rounding alone);
  sign_gap    the share of a fixed sample of coordinates (``SAMPLE`` per
              leaf, drawn from the seed) where the sign (-1, 0 or +1) of the
              first round's change differs from the reference's. A norm
              cannot see which coordinates the clients' signs agree on; this
              sees the payload bits under the round's client keys, and an
              update applied with the wrong sign.

A number without a limit (null) is reported and not compared.
"""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap", "sign_gap")

#: coordinates per leaf that ``sign_gap`` reads (all of a smaller leaf)
SAMPLE = 1 << 16


@jax.jit
def leaf_norms(a, b):
    """(n_leaves,) f32 norms of a - b per leaf, in tree order."""
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel()
                                      - y.astype(jnp.float32).ravel())
                      for x, y in zip(jax.tree_util.tree_leaves(a),
                                      jax.tree_util.tree_leaves(b))])


@functools.lru_cache(maxsize=None)
def _signs_fn(sizes: tuple):
    def signs(a, b, key):
        out = []
        for i, (x, y) in enumerate(zip(jax.tree_util.tree_leaves(a),
                                       jax.tree_util.tree_leaves(b))):
            d = x.astype(jnp.float32).ravel() - y.astype(jnp.float32).ravel()
            if sizes[i] > SAMPLE:
                d = d[jax.random.randint(jax.random.fold_in(key, i),
                                         (SAMPLE,), 0, sizes[i])]
            out.append(jnp.sign(d).astype(jnp.int8))
        return jnp.concatenate(out)
    return jax.jit(signs)


def change_signs(a, b, key) -> np.ndarray:
    """int8 signs of a - b at the sampled coordinates of every leaf (the
    same coordinates for the same key and shapes), in tree order."""
    sizes = tuple(int(x.size) for x in jax.tree_util.tree_leaves(a))
    return np.asarray(_signs_fn(sizes)(a, b, key))


def _leaf_gap(prog, ref, keep=None) -> float:
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    med = float(np.median(r))
    return float(np.max(np.abs(p - r) / np.maximum(np.maximum(r, med),
                                                   1e-30)))


def numbers(prog: dict, ref: dict) -> dict:
    """Readings of the program (or a stand-in for it) against the
    reference, both as ``Reference.run`` returns them."""
    grad = np.asarray(ref["grad"], np.float64)
    keep = grad >= np.median(grad) / 1000.0
    return {
        "loss_gap": float(max(abs(a - b)
                              for a, b in zip(prog["loss"], ref["loss"]))),
        "grad_gap": _leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": _leaf_gap(prog["change"], ref["change"], keep),
        "sign_gap": float(np.mean(np.asarray(prog["sign"])
                                  != np.asarray(ref["sign"]))),
    }


def load_limits(root: Path, cell: str) -> dict:
    return json.loads((root / "bench" / "limits" / f"{cell}.json").read_text())


def judge(values: dict, limits: dict) -> tuple:
    """-> (correct, {name: {"value": v, "limit": l}}) for every number."""
    checks, ok = {}, True
    for name in NAMES:
        v, lim = values[name], limits["limits"].get(name)
        checks[name] = {"value": v, "limit": lim}
        if not math.isfinite(v):
            ok = False
        elif lim is not None and v > lim:
            ok = False
    return ok, checks
