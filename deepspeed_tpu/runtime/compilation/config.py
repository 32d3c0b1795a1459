"""``"compilation"`` config block.

Key constants live in ``runtime/constants.py`` so the dslint DSC4xx
schema extractor validates unknown/misspelled keys for free (a
``"cach_dir"`` typo gets a "did you mean 'cache_dir'?" at engine
construction instead of silently compiling cold forever).
"""

from .. import constants as C
from ..config_utils import get_scalar_param


class DeepSpeedCompilationConfig:
    """Typed view of the ``compilation`` subsection (all keys optional)."""

    def __init__(self, param_dict):
        comp = param_dict.get(C.COMPILATION, {}) or {}
        self.cache = get_scalar_param(
            comp, C.COMPILATION_CACHE, C.COMPILATION_CACHE_DEFAULT)
        if not isinstance(self.cache, bool):
            raise ValueError(
                f"compilation.cache must be true or false, "
                f"got {self.cache!r}")
        cache_dir = get_scalar_param(
            comp, C.COMPILATION_CACHE_DIR, C.COMPILATION_CACHE_DIR_DEFAULT)
        self.cache_dir = str(cache_dir) if cache_dir else ""
        self.min_entry_size_bytes = int(get_scalar_param(
            comp, C.COMPILATION_MIN_ENTRY_SIZE_BYTES,
            C.COMPILATION_MIN_ENTRY_SIZE_BYTES_DEFAULT))
        if self.min_entry_size_bytes < 0:
            raise ValueError(
                "compilation.min_entry_size_bytes must be >= 0, got "
                f"{self.min_entry_size_bytes}")
        self.min_compile_secs = float(get_scalar_param(
            comp, C.COMPILATION_MIN_COMPILE_SECS,
            C.COMPILATION_MIN_COMPILE_SECS_DEFAULT))
        if self.min_compile_secs < 0:
            raise ValueError(
                "compilation.min_compile_secs must be >= 0, got "
                f"{self.min_compile_secs}")

    def __repr__(self):
        return (f"DeepSpeedCompilationConfig(cache={self.cache!r}, "
                f"cache_dir={self.cache_dir!r}, "
                f"min_entry_size_bytes={self.min_entry_size_bytes}, "
                f"min_compile_secs={self.min_compile_secs})")
