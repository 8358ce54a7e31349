"""FLOPS profiler.

Parity: reference ``deepspeed/profiling/flops_profiler/profiler.py``
(``FlopsProfiler`` :28, ``get_model_profile`` API, compute fns :507-830).

The reference monkey-patches ``torch.nn.functional`` to count MACs as eager
ops execute. Under JAX everything the step runs is visible in one jaxpr, so
the TPU-native design is *static analysis*: trace the function once with
``jax.make_jaxpr`` and walk the equations, counting FLOPs per primitive —
exact for matmuls/convs/elementwise, structure-aware for ``scan`` (× length),
``cond`` (max of branches) and remat (recompute counted once, like the
reference's ``recompute_fwd_factor``). Duration comes from a synchronized
wall-clock around the profiled step, and the per-module tree report is built
with ``flax``'s tabulate (XLA cost analysis per module).
"""

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ...telemetry.tracing import phase_of
from ...utils.logging import logger

# primitives counted as one FLOP per output element
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "neg", "abs", "sign", "floor", "ceil", "round",
    "exp", "exp2", "expm1", "log", "log1p", "sqrt", "rsqrt", "cbrt", "logistic", "tanh", "tan", "sin", "cos",
    "atan2", "erf", "erfc", "erf_inv", "integer_pow", "square", "reciprocal", "clamp", "nextafter",
    "eq", "ne", "ge", "gt", "le", "lt", "select_n", "is_finite", "sort", "add_any",
}
# primitives counted as one FLOP per *input* element (reductions)
_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "argmax", "argmin",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
}
_HIGHER_ORDER_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr", "body_jaxpr")


def _size(var) -> int:
    try:
        return int(np.prod(var.aval.shape)) if var.aval.shape else 1
    except Exception:
        return 0


def _sub_jaxprs(params: Dict[str, Any]):
    for key in _HIGHER_ORDER_JAXPR_PARAMS:
        if key in params and params[key] is not None:
            yield params[key]
    if "branches" in params:  # cond: handled by caller (max, not sum)
        return


def _as_jaxpr(obj):
    # params may hold a ClosedJaxpr or a raw Jaxpr
    return getattr(obj, "jaxpr", obj)


def _count_eqns(jaxpr, phases: Optional[Dict[str, float]] = None, scale: float = 1.0, stack: str = "") -> Tuple[float, float]:
    """Return (flops, macs) for one (open) jaxpr. ``phases``, where given,
    also takes every equation's FLOPs under the phase its name stack says
    (``telemetry/tracing.py::phase_of``: forward, recomputed, backward,
    update), ``scale`` times (the trips of an enclosing scan, the devices of
    a shard_map); ``stack`` is the enclosing equations' name stack, which an
    equation inside a ``checkpoint`` or a ``jit`` does not repeat."""
    flops = 0.0
    macs = 0.0

    def inner(sub, times=1.0):
        return _count_eqns(_as_jaxpr(sub), phases, scale * times, here)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        params = eqn.params
        here = f"{stack}/{eqn.source_info.name_stack}" if phases is not None else ""
        own = 0.0  # this equation's own FLOPs, what it encloses apart
        if name == "dot_general":
            ((lhs_c, rhs_c), (lhs_b, rhs_b)) = params["dimension_numbers"]
            lhs_shape = eqn.invars[0].aval.shape
            k = int(np.prod([lhs_shape[i] for i in lhs_c])) if lhs_c else 1
            out_elems = _size(eqn.outvars[0])
            macs += out_elems * k
            own = 2.0 * out_elems * k
        elif name == "conv_general_dilated":
            rhs_shape = eqn.invars[1].aval.shape
            dn = params["dimension_numbers"]
            in_features = rhs_shape[dn.rhs_spec[1]]  # feature_group_count is already reflected here
            kernel_spatial = int(np.prod([rhs_shape[i] for i in dn.rhs_spec[2:]])) if len(dn.rhs_spec) > 2 else 1
            out_elems = _size(eqn.outvars[0])
            per_out = in_features * kernel_spatial
            macs += out_elems * per_out
            own = 2.0 * out_elems * per_out
        elif name in ("scan",):
            length = int(params.get("length", 1))
            inner_f, inner_m = inner(params["jaxpr"], length)
            flops += inner_f * length
            macs += inner_m * length
        elif name == "shard_map":  # the body is ONE device's program, at its shapes: every device of the manual axes runs it
            devices = int(np.prod([params["mesh"].shape[a] for a in params["manual_axes"]]))
            inner_f, inner_m = inner(params["jaxpr"], devices)
            flops += inner_f * devices
            macs += inner_m * devices
        elif name in ("while",):
            body_f, body_m = inner(params["body_jaxpr"])
            flops += body_f  # trip count unknowable statically; count one iteration
            macs += body_m
        elif name in ("cond",):  # the dearest branch, and its phases
            best = (0.0, 0.0, {})
            for branch in params["branches"]:
                took: Dict[str, float] = {}
                bf, bm = _count_eqns(_as_jaxpr(branch), took if phases is not None else None, scale, here)
                best = max(best, (bf, bm, took), key=lambda t: t[0])
            flops += best[0]
            macs += best[1]
            for phase, f in best[2].items():
                phases[phase] = phases.get(phase, 0.0) + f
        elif name in _ELEMENTWISE:
            own = _size(eqn.outvars[0])
        elif name in _REDUCTIONS:
            own = _size(eqn.invars[0])
        elif name == "custom_jvp_call" or name == "custom_vjp_call" or name == "custom_vjp_call_jaxpr":
            sub = params.get("call_jaxpr") or params.get("fun_jaxpr")
            if sub is not None:
                f, m = inner(sub)
                flops += f
                macs += m
        else:
            counted = False
            for sub in _sub_jaxprs(params):
                f, m = inner(sub)
                flops += f
                macs += m
                counted = True
            if not counted and name in ("pallas_call",):
                # Pallas kernels are opaque here; approximate by output size
                own = sum(_size(v) for v in eqn.outvars)
        flops += own
        if own and phases is not None:
            phase = phase_of(here)
            phases[phase] = phases.get(phase, 0.0) + own * scale
    return flops, macs


def flops_of_jaxpr(closed_jaxpr, phases: Optional[Dict[str, float]] = None) -> Tuple[int, int]:
    """(flops, macs) of a ``ClosedJaxpr`` by structural walk; ``phases``, a
    dict, is filled with the same FLOPs by phase (``_count_eqns``)."""
    f, m = _count_eqns(_as_jaxpr(closed_jaxpr), phases)
    return int(f), int(m)


def flops_of_fn(fn: Callable, *args, phases: Optional[Dict[str, float]] = None, **kwargs) -> Tuple[int, int]:
    """Trace ``fn`` abstractly and count (flops, macs). Works on jitted fns.
    ``phases``: as ``flops_of_jaxpr`` (the same walk, the same trace)."""
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return flops_of_jaxpr(jaxpr, phases)


def breakdown_of_fn(fn: Callable, *args, **kwargs) -> Tuple[int, int, Dict[str, int]]:
    """(flops, macs, per-primitive flop breakdown) of ``fn`` on these args.

    The breakdown attributes whole control-flow regions (scan/while/cond)
    to their head primitive and descends through transparent call wrappers
    (pjit/remat). Shared with the serving cost-card builder
    (``telemetry/costs.py``) and the golden-count tests."""
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    f, m = flops_of_jaxpr(jaxpr)
    return f, m, FlopsProfiler._primitive_breakdown(jaxpr)


# -------------------- string formatting (reference profiler.py:905-960) ----
def number_to_string(num, units=None, precision=2) -> str:
    if units is None:
        if abs(num) >= 1e12:
            return f"{num / 1e12:.{precision}f} T"
        if abs(num) >= 1e9:
            return f"{num / 1e9:.{precision}f} G"
        if abs(num) >= 1e6:
            return f"{num / 1e6:.{precision}f} M"
        if abs(num) >= 1e3:
            return f"{num / 1e3:.{precision}f} K"
        return f"{num:.{precision}f} "
    scale = {"T": 1e12, "G": 1e9, "M": 1e6, "K": 1e3, "": 1.0}[units]
    return f"{num / scale:.{precision}f} {units}"


def flops_to_string(flops, units=None, precision=2) -> str:
    return number_to_string(flops, units, precision) + "FLOPS"


def macs_to_string(macs, units=None, precision=2) -> str:
    return number_to_string(macs, units, precision) + "MACs"


def params_to_string(params_num, units=None, precision=2) -> str:
    return number_to_string(params_num, units, precision).rstrip()


def duration_to_string(duration, units=None, precision=2) -> str:
    if units is None:
        if duration >= 1:
            return f"{duration:.{precision}f} s"
        if duration >= 1e-3:
            return f"{duration * 1e3:.{precision}f} ms"
        return f"{duration * 1e6:.{precision}f} us"
    scale = {"s": 1.0, "ms": 1e-3, "us": 1e-6}[units]
    return f"{duration / scale:.{precision}f} {units}"


def _params_of_tree(tree) -> int:
    return sum(int(np.prod(x.shape)) if getattr(x, "shape", ()) else 1 for x in jax.tree_util.tree_leaves(tree))


class FlopsProfiler:
    """Profiles one training/inference step: static FLOPs + measured latency.

    Reference: ``FlopsProfiler`` (``profiling/flops_profiler/profiler.py:28``).
    The reference counts the forward pass as ops execute; here the profiled
    callable is whatever the engine jits (fwd, or fused fwd+bwd), so the
    counts cover exactly what runs on device.
    """

    def __init__(self, model=None, ds_engine=None, recompute_fwd_factor: float = 0.0):
        self.model = model
        self.ds_engine = ds_engine
        self.recompute_fwd_factor = recompute_fwd_factor
        self.started = False
        self._t0 = 0.0
        self._duration = 0.0
        self._flops = 0
        self._macs = 0
        self._params = 0
        self._per_primitive: Dict[str, int] = {}

    # -- lifecycle (reference API) --
    def start_profile(self, ignore_list=None):
        self.started = True
        self._duration = 0.0
        self._t0 = time.perf_counter()

    def stop_profile(self):
        if self.started:
            import jax.numpy as jnp
            (jnp.zeros(()) + 0).block_until_ready()  # drain async dispatch
            self._duration = time.perf_counter() - self._t0

    def end_profile(self):
        self.started = False

    def reset_profile(self):
        self._flops = self._macs = self._params = 0
        self._duration = 0.0

    # -- static analysis --
    def analyze_fn(self, fn: Callable, *args, params_tree=None):
        jaxpr = jax.make_jaxpr(fn)(*args)
        self._flops, self._macs = flops_of_jaxpr(jaxpr)
        self._per_primitive = self._primitive_breakdown(jaxpr)
        if params_tree is not None:
            self._params = _params_of_tree(params_tree)
        return self._flops, self._macs

    @staticmethod
    def _primitive_breakdown(closed_jaxpr) -> Dict[str, int]:
        out: Dict[str, int] = {}

        # _count_eqns recurses into scan/cond/while bodies itself, so whole
        # control-flow regions are attributed to their head primitive; plain
        # call wrappers (pjit/remat) are transparent — descend instead
        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                name = eqn.primitive.name
                if name in ("pjit", "closed_call", "core_call", "remat", "checkpoint", "custom_jvp_call",
                            "custom_vjp_call"):
                    for sub in _sub_jaxprs(eqn.params):
                        walk(_as_jaxpr(sub))
                    continue
                single = type("J", (), {"eqns": [eqn]})
                f, _ = _count_eqns(single)
                if f:
                    out[name] = out.get(name, 0) + int(f)

        walk(_as_jaxpr(closed_jaxpr))
        return out

    # -- getters (reference profiler.py:200-260) --
    def get_total_flops(self, as_string=False):
        total = int(self._flops * (1.0 + self.recompute_fwd_factor))
        return flops_to_string(total) if as_string else total

    def get_total_macs(self, as_string=False):
        return macs_to_string(self._macs) if as_string else self._macs

    def get_total_params(self, as_string=False):
        return params_to_string(self._params) if as_string else self._params

    def get_total_duration(self, as_string=False):
        return duration_to_string(self._duration) if as_string else self._duration

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=1, detailed=True, output_file=None):
        lines = [
            "-------------------------- DeepSpeed-TPU Flops Profiler --------------------------",
            f"Profile at step {profile_step}:",
            f"  params:               {params_to_string(self._params)}",
            f"  fwd(+bwd) MACs:       {macs_to_string(self._macs)}",
            f"  fwd(+bwd) FLOPs:      {flops_to_string(self.get_total_flops())}",
            f"  step latency:         {duration_to_string(self._duration)}",
        ]
        if self._duration > 0:
            lines.append(f"  achieved throughput:  {flops_to_string(self.get_total_flops() / self._duration)}/s")
        if detailed and self._per_primitive:
            lines.append("  FLOPs by primitive:")
            for name, f in sorted(self._per_primitive.items(), key=lambda kv: -kv[1]):
                lines.append(f"    {name:<24s} {flops_to_string(f)}")
        lines.append("-" * 82)
        text = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as fh:
                fh.write(text + "\n")
        else:
            logger.info(text)
        return text


def get_model_profile(model=None,
                      input_shape=None,
                      args=(),
                      kwargs=None,
                      fn: Optional[Callable] = None,
                      print_profile=True,
                      detailed=True,
                      module_depth=-1,
                      top_modules=1,
                      as_string=True,
                      output_file=None,
                      ignore_modules=None,
                      mode="forward"):
    """Profile a model or plain callable; returns ``(flops, macs, params)``.

    Reference: ``get_model_profile`` (``profiler.py:1150``). Accepts either a
    flax module (``model`` + ``input_shape`` of int32 token ids, or explicit
    ``args``) or any jittable ``fn`` + ``args``.
    """
    kwargs = kwargs or {}
    prof = FlopsProfiler(model=model)
    if fn is None:
        if model is None:
            raise ValueError("need a flax `model` or a callable `fn`")
        if not args:
            if input_shape is None:
                raise ValueError("need `input_shape` or `args` for a flax model")
            args = (np.zeros(input_shape, dtype=np.int32),)
        variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), *args))
        prof._params = _params_of_tree(variables)
        # our CausalLM-style wrappers init from a batch dict but apply on ids
        apply_args = args
        if args and isinstance(args[0], dict) and "input_ids" in args[0]:
            apply_args = (args[0]["input_ids"],) + tuple(args[1:])
        jaxpr = jax.make_jaxpr(lambda v, *a: model.apply(v, *a, **kwargs))(variables, *apply_args)
        prof._flops, prof._macs = flops_of_jaxpr(jaxpr)
        prof._per_primitive = prof._primitive_breakdown(jaxpr)
    else:
        prof.analyze_fn(fn, *args)
    if print_profile:
        prof.print_model_profile(module_depth=module_depth, top_modules=top_modules, detailed=detailed,
                                 output_file=output_file)
    if as_string:
        return (prof.get_total_flops(as_string=True), prof.get_total_macs(as_string=True),
                prof.get_total_params(as_string=True))
    return prof.get_total_flops(), prof.get_total_macs(), prof.get_total_params()
