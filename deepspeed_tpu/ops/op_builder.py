"""Op registry — the TPU analog of the reference's ``op_builder`` package.

The reference (``op_builder/builder.py:78-260``) compiles CUDA extensions
ahead-of-time or JIT (ninja), with per-op compatibility checks against the
local torch/CUDA install, and a registry ``ALL_OPS`` consumed by setup.py
and ``ds_report``.  Under JAX there is nothing to compile at install time —
"ops" are jitted XLA programs and Pallas kernels compiled on first trace —
so a builder here is a *capability probe + loader*: ``is_compatible()``
answers whether this platform can run the op's fast path, and ``load()``
returns the op's entry point (triggering any lazy imports), mirroring the
reference's ``OpBuilder.load()`` contract.
"""

import hashlib
import importlib
import os
import shutil
import subprocess
import tempfile

# native sources ship inside the package (deepspeed_tpu/csrc/...) so an
# installed wheel can JIT-build them, unlike the reference's repo-root csrc/
_PKG_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_CACHE_DIR = os.environ.get(
    "DS_BUILD_CACHE",
    os.path.join(os.path.expanduser("~"), ".cache", "deepspeed_tpu"))


def jit_build(name, sources, extra_flags=()):
    """Compile C++ sources into a cached shared object and return its path
    — the analog of the reference's ninja JIT load
    (``op_builder/builder.py:170-220``).  Cache key = source contents +
    flags; rebuilds only when they change."""
    gxx = shutil.which("g++")
    if gxx is None:
        raise RuntimeError(f"op {name!r} needs g++ to JIT-build its native "
                           "kernel; none found on PATH")
    paths = [os.path.join(_PKG_ROOT, s) for s in sources]
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(repr(extra_flags).encode())
    # -march=native output is host-CPU-specific and $HOME may be shared
    # (NFS) across heterogeneous hosts: key the cache on toolchain + CPU
    try:
        h.update(subprocess.run([gxx, "--version"], capture_output=True,
                                text=True).stdout.encode())
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    h.update(line.encode())
                    break
    except Exception:  # dslint: disable=DSE502 -- host-fingerprint probe; a partial hash only weakens cache keying
        pass
    base_flags = ["-O3", "-shared", "-fPIC", "-std=c++17"]
    tiers = [base_flags + ["-march=native", "-fopenmp"],
             base_flags + ["-fopenmp"],
             base_flags]
    os.makedirs(_CACHE_DIR, exist_ok=True)
    last_err = None
    for tier_idx, flags in enumerate(tiers):
        out = os.path.join(
            _CACHE_DIR, f"{name}-{h.hexdigest()[:16]}-t{tier_idx}.so")
        if os.path.exists(out):
            return out
        # unique temp per process: concurrent builders (multi-process
        # launch, cold cache) must not interleave writes; os.replace makes
        # the publish atomic and last-writer-wins is fine (same content)
        fd, tmp = tempfile.mkstemp(dir=_CACHE_DIR, suffix=".so.tmp")
        os.close(fd)
        cmd = [gxx, *flags, *extra_flags, "-o", tmp, *paths]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode == 0:
            os.replace(tmp, out)
            return out
        os.unlink(tmp)
        last_err = proc.stderr
    raise RuntimeError(f"g++ failed building op {name!r}:\n{last_err}")


class OpBuilder:
    """Base op record (reference ``op_builder/builder.py:78``)."""

    NAME = "op"
    MODULE = None       # dotted path relative to deepspeed_tpu
    ENTRY = None        # attribute to return from load()

    def absolute_name(self):
        return f"deepspeed_tpu.{self.MODULE}"

    def is_compatible(self):
        ok, _ = self.compatibility()
        return ok

    def compatibility(self):
        """(ok, detail) — platform-dependent checks live in subclasses."""
        return True, "pure-XLA op (always available)"

    def load(self):
        """Import and return the op entry point (the reference's JIT-load;
        here the compile happens lazily on first trace)."""
        mod = importlib.import_module(self.absolute_name())
        return getattr(mod, self.ENTRY) if self.ENTRY else mod


def _backend():
    import jax

    return jax.default_backend()


def _has_memory(kind):
    import jax

    try:
        jax.devices()[0].memory(kind)
        return True
    except Exception:
        return False


class FusedAdamBuilder(OpBuilder):
    NAME = "fused_adam"
    MODULE = "ops.adam.fused_adam"
    ENTRY = "FusedAdam"


class FusedLambBuilder(OpBuilder):
    NAME = "fused_lamb"
    MODULE = "ops.lamb.fused_lamb"
    ENTRY = "FusedLamb"


class FlashAttentionBuilder(OpBuilder):
    NAME = "flash_attention"
    MODULE = "ops.transformer.flash_attention"
    ENTRY = "flash_attention"

    def compatibility(self):
        if _backend() != "tpu":
            return False, "compiled Mosaic kernels need a TPU (interpret mode elsewhere)"
        return True, "Pallas kernel; engaged when score memory exceeds budget"


class SparseAttentionBuilder(OpBuilder):
    NAME = "sparse_attention"
    MODULE = "ops.sparse_attention"
    ENTRY = "block_sparse_attention"


class SparseFlashAttentionBuilder(OpBuilder):
    """LUT-driven Pallas block-sparse flash kernel (the reference's Triton
    SDD/DSD/DDS + softmax stack as one Mosaic kernel family)."""

    NAME = "sparse_flash_attention"
    MODULE = "ops.sparse_attention.flash_block_sparse"
    ENTRY = "flash_block_sparse_attention"

    def compatibility(self):
        if _backend() != "tpu":
            return False, "compiled Mosaic kernels need a TPU (gather path elsewhere)"
        return True, "engaged for 128-multiple layout blocks (block >= 512 advised)"


class RingAttentionBuilder(OpBuilder):
    NAME = "ring_attention"
    MODULE = "ops.transformer.ring_attention"
    ENTRY = "ring_attention"


class OnebitAdamBuilder(OpBuilder):
    NAME = "onebit_adam"
    MODULE = "runtime.fp16.onebit_adam"
    ENTRY = "OnebitAdam"


class CPUAdamBuilder(OpBuilder):
    """The native host Adam kernel (reference ``csrc/adam/cpu_adam.cpp``):
    C++ (OpenMP, compiler-vectorized) JIT-built with g++, driven through
    ``jax.pure_callback``.  Pairs with the pinned_host state of
    ZeRO-Offload."""

    NAME = "cpu_adam"
    MODULE = "ops.adam.cpu_adam"
    ENTRY = "DeepSpeedCPUAdam"

    def compatibility(self):
        import shutil as _sh

        if _sh.which("g++") is None:
            return False, "g++ not found (native kernel JIT build)"
        detail = "C++ host kernel (JIT-built)"
        if not _has_memory("pinned_host"):
            detail += "; no pinned_host space — offload state stays on device"
        return True, detail


class ActivationOffloadBuilder(OpBuilder):
    NAME = "activation_offload"
    MODULE = "runtime.activation_checkpointing.checkpointing"
    ENTRY = "make_remat_policy"

    def compatibility(self):
        if not _has_memory("pinned_host"):
            return False, "no pinned_host memory space"
        if _backend() != "tpu":
            return False, "remat offload needs in-jit memory placement (TPU)"
        return True, "save_and_offload remat policy"


class TransformerBuilder(OpBuilder):
    NAME = "transformer"
    MODULE = "models.layers"
    ENTRY = "TransformerLayer"


ALL_OPS = {b.NAME: b for b in (
    FusedAdamBuilder(), FusedLambBuilder(), FlashAttentionBuilder(),
    SparseAttentionBuilder(), SparseFlashAttentionBuilder(),
    RingAttentionBuilder(), OnebitAdamBuilder(),
    CPUAdamBuilder(), ActivationOffloadBuilder(), TransformerBuilder(),
)}


def get_op_builder(name):
    return ALL_OPS[name]
