"""Flops profiler: analytic jaxpr cost analysis + per-scope breakdown.

TPU-native re-design of the reference flops profiler
(``deepspeed/profiling/flops_profiler/profiler.py:11-814``).  The reference
monkey-patches ``torch.nn.functional`` and installs module hooks to count
MACs at runtime; under JAX the whole computation is available *statically*
as a jaxpr, so the profiler

- walks the jaxpr (through ``pjit``/``scan``/``cond``/``remat`` inner
  jaxprs, multiplying scan bodies by their trip count) counting matmul /
  conv / elementwise FLOPs analytically,
- attributes them to ``jax.named_scope`` paths (the analog of the
  reference's per-module table; models in ``deepspeed_tpu.models`` name
  their layers), and
- cross-checks against the backend's compiled cost analysis when the
  platform provides one (``Compiled.cost_analysis()``).

Profiling the *training* step needs no 3x heuristic: tracing
``value_and_grad`` (or the engine's fused step) yields the backward ops in
the jaxpr and they are counted exactly.
"""

from collections import defaultdict

import jax
import numpy as np

from ...utils.logging import logger


def _aval_size(aval):
    return int(np.prod(aval.shape)) if aval.shape else 1


def _dot_general_flops(eqn):
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = int(np.prod([lhs.shape[d] for d in lb])) if lb else 1
    contract = int(np.prod([lhs.shape[d] for d in lc])) if lc else 1
    lhs_free = _aval_size(lhs) // max(batch * contract, 1)
    rhs_free = _aval_size(rhs) // max(batch * contract, 1)
    return 2 * batch * lhs_free * rhs_free * contract


def _conv_flops(eqn):
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    # 2 * output elements * kernel elements per output channel
    dn = eqn.params["dimension_numbers"]
    out_feature_dim = dn.out_spec[1]
    kernel_size = _aval_size(rhs) // max(out.shape[out_feature_dim], 1)
    return 2 * _aval_size(out) * kernel_size


# elementwise / reduction primitives counted as one op per output element
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "exp", "log", "tanh", "pow",
    "rsqrt", "sqrt", "neg", "logistic", "erf", "integer_pow", "and", "or",
    "xor", "select_n",
}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "argmax", "argmin"}


def count_jaxpr_flops(jaxpr, by_scope=None, scale=1):
    """FLOPs of one execution of a jaxpr.  ``by_scope`` (optional dict)
    accumulates per-``named_scope`` totals, pre-multiplied by ``scale`` (the
    product of enclosing loop trip counts)."""
    total = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            length = int(eqn.params.get("length", 1))
            total += length * count_jaxpr_flops(
                eqn.params["jaxpr"].jaxpr, by_scope, scale * length)
            continue
        if prim == "cond":
            branches = eqn.params["branches"]
            if not branches:
                continue
            counts = [count_jaxpr_flops(b.jaxpr, None, scale) for b in branches]
            hot = int(np.argmax(counts))
            if by_scope is not None:
                count_jaxpr_flops(branches[hot].jaxpr, by_scope, scale)
            total += counts[hot]
            continue
        if prim == "while":
            # trip count is data-dependent: count one iteration (caveat
            # matches the reference's inability to see dynamic loops)
            total += count_jaxpr_flops(eqn.params["body_jaxpr"].jaxpr,
                                       by_scope, scale)
            continue
        inner = None
        for key in ("jaxpr", "call_jaxpr"):
            if key in eqn.params:
                inner = eqn.params[key]
                inner = getattr(inner, "jaxpr", inner)
                break
        if inner is not None:
            total += count_jaxpr_flops(inner, by_scope, scale)
            continue

        if prim == "dot_general":
            sub = _dot_general_flops(eqn)
        elif prim == "conv_general_dilated":
            sub = _conv_flops(eqn)
        elif prim in _ELEMENTWISE:
            sub = _aval_size(eqn.outvars[0].aval)
        elif prim in _REDUCE:
            sub = _aval_size(eqn.invars[0].aval)
        else:
            continue
        total += sub
        if by_scope is not None and sub:
            scope = str(eqn.source_info.name_stack) or "<top>"
            by_scope[scope] += sub * scale
    return total


def count_fn_flops(fn, *args, by_scope=None, **kwargs):
    """FLOPs of ``fn(*args, **kwargs)`` (fn may be jitted — tracing goes
    through).  Returns (flops, by_scope or None)."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    scope = defaultdict(int) if by_scope is None else by_scope
    flops = count_jaxpr_flops(closed.jaxpr, scope)
    return flops, dict(scope)


def params_count(params):
    return int(sum(_aval_size(x) for x in jax.tree_util.tree_leaves(params)))


def backend_cost_analysis(jitted_fn, *args, **kwargs):
    """The compiled executable's own cost model, where the backend provides
    one (flops, bytes accessed).  Returns {} when unavailable."""
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost or {})
    except Exception as e:  # pragma: no cover - backend specific
        logger.debug(f"backend cost analysis unavailable: {e}")
        return {}


def _fmt(n):
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n:.0f} "


def get_model_profile(model=None, batch=None, params=None, fn=None, args=None,
                      train=False, rng=None, as_string=False, top_modules=3,
                      print_profile=True):
    """Profile a model or bare function (reference ``get_model_profile``,
    ``profiler.py:738``).

    Either ``model`` (with ``.init``/``.apply``) plus ``batch``, or ``fn``
    plus ``args``.  ``train=True`` profiles the full fwd+bwd
    (``value_and_grad``) instead of applying a 3x heuristic.  Returns
    ``(flops, macs, params)`` — formatted strings if ``as_string``.
    """
    if fn is None:
        assert model is not None and batch is not None
        if params is None:
            params = model.init(rng if rng is not None else jax.random.PRNGKey(0))
        if train:
            def fn(p, b):
                return jax.grad(
                    lambda q: model.apply(q, b, rng=None, train=True)
                    .astype(np.float32).sum())(p)
        else:
            def fn(p, b):
                return model.apply(p, b, rng=None, train=False)
        args = (params, batch)
    n_params = params_count(args[0]) if args else 0
    flops, by_scope = count_fn_flops(fn, *args)
    macs = flops // 2
    if print_profile:
        prof = FlopsProfile(flops=flops, macs=macs, params=n_params,
                            by_scope=by_scope)
        prof.print(top_modules=top_modules)
    if as_string:
        return f"{_fmt(flops)}FLOPs", f"{_fmt(macs)}MACs", f"{_fmt(n_params)}params"
    return flops, macs, n_params


class FlopsProfile:
    def __init__(self, flops, macs, params, by_scope=None, wall_ms=None,
                 backend_cost=None, device_ms_by_scope=None):
        self.flops = flops
        self.macs = macs
        self.params = params
        self.by_scope = by_scope or {}
        self.wall_ms = wall_ms
        self.backend_cost = backend_cost or {}
        # {(scope path, direction): device ms a step}, as
        # telemetry.scopes.ms_per_run gives them from a scopes.json
        self.device_ms_by_scope = device_ms_by_scope or {}

    def by_program_scope(self):
        """The FLOPs by the program's own scopes and direction (``by_scope``
        is keyed by JAX's name stacks; ``telemetry.scopes.scope_of`` reads
        both the same way), beside the device milliseconds where given:
        ``{(path, direction): (flops, ms or None)}``."""
        from ...telemetry.scopes import scope_of

        flops = defaultdict(int)
        for stack, count in self.by_scope.items():
            # scope_of drops the primitive at a name's end: stand one in
            flops[scope_of(f"{stack}/_")] += count
        return {key: (count, self.device_ms_by_scope.get(key))
                for key, count in flops.items()}

    def achieved_tflops(self):
        if not self.wall_ms:
            return None
        return self.flops / (self.wall_ms / 1e3) / 1e12

    def mfu(self, device=None):
        """Model-FLOPs utilisation against the chip's bf16 peak (the
        table in ``profiling/utilization.py``).  None without a
        wall time or on a device that is not a TPU (it has no peak)."""
        if not self.wall_ms:
            return None
        from ..utilization import chip_peak_tflops

        if device is None:
            import jax

            device = jax.devices()[0]
        peak = chip_peak_tflops(device)
        return None if peak is None else self.achieved_tflops() / peak

    def print(self, top_modules=3, log=None):
        log = log or logger.info
        log(f"flops profile: {_fmt(self.flops)}FLOPs, {_fmt(self.macs)}MACs, "
            f"{_fmt(self.params)}params")
        if self.wall_ms:
            mfu = self.mfu()
            log(f"  wall: {self.wall_ms:.2f} ms -> "
                f"{self.achieved_tflops():.2f} TFLOP/s achieved"
                + (f" (MFU {mfu:.3f})" if mfu is not None else ""))
        if self.backend_cost.get("flops"):
            log(f"  backend cost model: {_fmt(self.backend_cost['flops'])}FLOPs")
        scopes = sorted(self.by_scope.items(), key=lambda kv: -kv[1])
        for name, fl in scopes[:top_modules]:
            log(f"  {100.0 * fl / max(self.flops, 1):5.1f}%  {_fmt(fl)}FLOPs  {name}")
        if not self.device_ms_by_scope:
            return
        # the reference's latency column: device time and achieved rate
        # beside each scope's FLOPs
        rows = sorted(self.by_program_scope().items(),
                      key=lambda kv: -(kv[1][1] or 0.0))
        for (path, direction), (fl, ms) in rows[:max(top_modules, 10)]:
            name = path + (f".{direction}" if direction else "")
            rate = (f"{ms:9.3f} ms  {fl / (ms * 1e-3) / 1e12:7.2f} TFLOP/s"
                    if ms else "        - ms        - TFLOP/s")
            log(f"  {_fmt(fl):>9}FLOPs  {rate}  {name or '<top>'}")


class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler``,
    ``profiler.py:11``): profiles the engine's *actual* fused train step —
    forward, backward, optimizer, and collectives as traced — at the
    configured ``profile_step``."""

    def __init__(self, engine):
        self.engine = engine
        self.profile = None

    def profile_train_step(self, batch, wall_ms=None,
                           device_ms_by_scope=None):
        """``device_ms_by_scope``: ``{(scope path, direction): ms a step}``
        of a device trace read by scope (``telemetry.scopes.ms_per_run`` of
        the ``jit_train_step`` entry of a trigger's ``scopes.json``); the
        profile then prints device ms and achieved TFLOP/s beside each
        scope's FLOPs.  No trace is started here."""
        eng = self.engine
        flops, by_scope = count_fn_flops(
            eng._fwd_bwd_fn, eng._forward_params(), eng._shard_batch(batch),
            jax.random.PRNGKey(0), np.float32(1.0), {})
        # optimizer apply cost (elementwise over the flat space); a
        # master-shaped placeholder stands in for the gradient operand
        flat_g_like = eng.state["master"]
        apply_flops, apply_scopes = count_fn_flops(
            eng._apply_fn, eng.state["master"], eng.state["opt"],
            eng.state["scale"], eng.state["skipped"], flat_g_like,
            eng._device_hyperparams(), eng._segment_ids)
        for scope, count in apply_scopes.items():
            by_scope[scope] = by_scope.get(scope, 0) + count
        total = flops * eng.gradient_accumulation_steps() + apply_flops
        self.profile = FlopsProfile(
            flops=total, macs=total // 2,
            params=params_count(eng._param_template), by_scope=by_scope,
            wall_ms=wall_ms, device_ms_by_scope=device_ms_by_scope)
        return self.profile

    def print_model_profile(self, batch=None, top_modules=3):
        if self.profile is None:
            assert batch is not None, "first call needs a sample batch"
            self.profile_train_step(batch)
        self.profile.print(top_modules=top_modules)

