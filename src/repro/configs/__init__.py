from repro.configs.common import ArchConfig, get_arch, list_archs  # noqa: F401
