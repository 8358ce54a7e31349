"""Whether a call takes its Pallas kernel and how the kernel sits on the mesh: the one rule (``kernel_path``), the one
reader of the live mesh in ``ops/``, ``moe/`` and ``models/``, and the count of the choice (``counted``: made while a
program is traced, with the word chosen, by the site that chose or by the kernel's own call with what it alone knows; a
model's layer counts no path of its own). A site hands in what it alone knows: its ``fits``, whether it has specs, its
two forms; docs/ARCHITECTURE.md, "How a kernel is chosen and placed", has the table of sites. ``ops/registry.py`` is the
older mechanism, by name: ``attention``, the norms, Adam and the quantizers enter through it (ROADMAP.md, D20)."""

import jax
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import fit_spec, get_mesh_topology, prune_spec
from ..telemetry.tracing import region
from .registry import pallas_available


def interpret() -> bool:
    """Off the TPU a kernel can only be interpreted (a test that steers a call onto the kernels' path)."""
    return not pallas_available()


def kernel_path(fits: bool = True, has_specs: bool = True) -> str:
    """THE RULE: ``kernel`` where the backend compiles Mosaic, the site's shapes fit its kernels (``fits``: the site's own
    test) and the kernel can sit on the live mesh: the site has specs for it (then ``on_mesh`` places it) or the mesh is
    one chip; else ``xla``, the site's plain form. GSPMD cannot partition a Mosaic call, so a site without specs yet
    (``has_specs=False``: the selective scan, the short convolution, the sparse mixer) takes XLA's form on several chips."""
    topo = get_mesh_topology(required=False)
    return "kernel" if pallas_available() and fits and (has_specs or topo is None or topo.n_devices == 1) else "xla"


def counted(op: str, path: str, pass_: str = "fwd", name: str = "mixer/kernel", **labels):
    """The region ``name`` of a call site whose ``op`` was traced as ``path``, counted (one a call site a trace)."""
    return region(name, op=op, path=path, **{"pass": pass_}, **labels)


def count(op: str, path: str, pass_: str = "fwd", **labels):
    """``counted`` around nothing: the call's regions are what they were."""
    with counted(op, path, pass_, **labels):
        pass


def axis_size(axis: str) -> int:
    """The live mesh's size along ``axis``; 1 without a mesh."""
    topo = get_mesh_topology(required=False)
    return 1 if topo is None else topo.axis_size(axis)


def batch_spec(shape, *rest) -> P:
    """How an operand of ``shape`` splits over the live mesh: its leading dimension over the batch axes and the others
    as ``rest`` says (``"tensor"`` for a dimension of heads), each only where its axes are wider than one and divide
    the dimension. ``P()`` without a mesh."""
    topo = get_mesh_topology(required=False)
    return P() if topo is None else fit_spec(prune_spec(P(topo.batch_axes, *rest), topo), shape, topo)


HELD = P(("expert", "fsdp"), None, None)  # a routed layer's experts, by their leading dimension: ``held_axes``


def held_axes(shape) -> tuple:
    """The live mesh's axes that a routed layer's held experts (a leaf of ``shape``, experts leading) are split over, in
    the order their blocks are numbered: ``expert`` and ``fsdp`` (``HELD``, the leaves' partition rule), each where it is
    wider than one, and all or none by whether their product divides the experts. ``()`` without a mesh, and inside
    another's manual region, which has gathered what it divided."""
    if not placed():
        return ()
    topo = get_mesh_topology()
    first = (tuple(fit_spec(prune_spec(HELD, topo), shape, topo)) + (None,))[0]
    return () if first is None else first if isinstance(first, tuple) else (first,)


def placed() -> bool:
    """Whether ``on_mesh`` wraps a function here: a live mesh of several chips, and not inside another's manual region."""
    topo = get_mesh_topology(required=False)
    return topo is not None and topo.n_devices > 1 and not jax.sharding.get_abstract_mesh().manual_axes


def on_mesh(fn, in_specs, out_specs):
    """``fn`` made safe to trace under a multi-device jit.

    GSPMD cannot partition a Mosaic kernel (the TPU lowering raises "Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in a
    shard_map"): on a mesh of several chips the kernel has to sit in a
    ``shard_map`` that is manual over every mesh axis, with specs that say
    how its operands split. Returns ``fn`` unchanged where that does not
    apply: no mesh, one device, or already inside a manual region (the
    ZeRO++ and tensor-parallel serving stacks).
    """
    if not placed():
        return fn
    return jax.shard_map(fn, mesh=get_mesh_topology().mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


def replicated_on_mesh(fn):
    """``on_mesh`` for a kernel whose operands are whole on every device
    (the serving norms outside the tensor-parallel region): each device
    runs it on its own copy, which is what GSPMD does with replicated
    operands anyway."""
    def call(*args):
        return on_mesh(fn, jax.tree_util.tree_map(lambda _: P(), args), P())(*args)
    return call
