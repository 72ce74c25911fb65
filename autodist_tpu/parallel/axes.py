"""Logical-axis sharding system + ParallelSpec.

The reference expresses distribution as per-variable protobuf nodes
(strategy.proto:30-69) because its substrate is graph surgery. The
TPU-native functional path expresses it as *logical axis rules*: every
parameter (and key activations) carries a tuple of logical axis names
(``('embed', 'mlp')``); a rule table maps logical axes to mesh axes; the
compiler binds params to ``NamedSharding``s and lets GSPMD insert the
collectives. This is the sharding recipe of the public scaling-book /
GSPMD lineage, replacing the reference's kernel layer for compute
parallelism (which the reference never had — SURVEY.md §2.3).

``ParallelSpec`` is the user-facing knob: sizes for the five mesh axes
(dp/tp/pp/sp/ep) plus rematerialization and ZeRO options. It serializes
like a reference Strategy so chief-built specs ship to workers unchanged.
"""
import threading
from dataclasses import dataclass, field, asdict

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu.const import (AXIS_DATA, AXIS_EXPERT, AXIS_MODEL,
                                AXIS_PIPELINE, AXIS_SEQUENCE)

# Default logical-axis -> mesh-axis rules. First match wins; a logical
# axis absent from the table is unsharded. ``batch`` rides the data axis,
# sequence rides the context-parallel axis, and the two classic Megatron
# families (hidden-expanding vs hidden-contracting matmul dims) ride the
# tensor axis.
DEFAULT_RULES = (
    ('batch', AXIS_DATA),
    ('seq', AXIS_SEQUENCE),
    ('embed', None),
    ('mlp', AXIS_MODEL),
    ('heads', AXIS_MODEL),
    ('kv', None),
    ('vocab', AXIS_MODEL),
    ('expert', AXIS_EXPERT),
    ('stage', AXIS_PIPELINE),
    ('classes', None),
)


@dataclass
class ParallelSpec:
    """Mesh-axis sizes + execution options for the functional path.

    dp/tp/pp/sp/ep: data / tensor / pipeline / sequence(context) / expert
    parallel degrees. ``dp=0`` means "use all remaining devices".
    ``zero``: optimizer-state sharding stage (1 = replicated state,
    2 = shard opt state over dp, 3 = also shard params over dp).
    ``remat``: 'none' | 'full' — jax.checkpoint policy on the step.
    """
    dp: int = 0
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1
    # multi-slice factor: the data axis is split this many ways across
    # slice (DCN) boundaries, so DP gradient reduction is the only
    # cross-DCN traffic while tp/pp/sp/ep stay on ICI within a slice.
    # Must divide the resolved dp.
    dcn_dp: int = 1
    zero: int = 1
    remat: str = 'none'
    microbatches: int = 1          # pipeline microbatches (pp>1)
    # 'gpipe' | '1f1b': 1f1b keeps only each rank's microbatch share
    # resident (+ per-microbatch remat); gpipe holds full input/output
    # stacks on every rank but accepts ragged microbatch counts
    pp_schedule: str = 'gpipe'
    # fused-1F1B backward variant: 'remat' (pp-bounded activation
    # stash, ~3 fwd passes), 'stash' (one boundary activation per
    # microbatch, ~2 fwd passes), 'auto' (stash while it fits
    # AUTODIST_PP_STASH_LIMIT_MB per rank)
    pp_variant: str = 'auto'
    sp_mode: str = 'ring'          # 'ring' | 'ulysses' (sp>1 attention)
    grad_accum: int = 1            # gradient-accumulation chunks
    rules: list = field(default_factory=lambda: [list(r)
                                                 for r in DEFAULT_RULES])

    # -- serialization (parity with Strategy JSON round-trip) -------------
    def to_dict(self):
        return asdict(self)

    @classmethod
    def from_dict(cls, d):
        """Tolerates version skew in BOTH directions: missing fields
        take their defaults (old dict, new code) and unknown fields are
        dropped with a warning (new dict, old code) — a chief and its
        workers need not run identical builds to exchange specs."""
        import dataclasses
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            from autodist_tpu.utils import logging
            logging.warning('ParallelSpec.from_dict: dropping unknown '
                            'fields %s (newer peer?)', sorted(unknown))
        return cls(**{k: v for k, v in d.items() if k in known})

    def resolve_dp(self, n_devices):
        fixed = self.tp * self.pp * self.sp * self.ep
        if self.dp:
            return self.dp
        if n_devices % fixed:
            raise ValueError(
                'tp*pp*sp*ep=%d does not divide device count %d'
                % (fixed, n_devices))
        return n_devices // fixed

    def build_mesh(self, devices=None):
        """Mesh with axes (data, pipe, seq, expert, model); size-1 axes kept.

        Axis order puts ``model`` (highest-traffic collectives) innermost so
        tensor-parallel groups land on adjacent ICI neighbors, then expert,
        seq, pipe, with data outermost — the standard hierarchy-matching
        layout.
        """
        devices = list(devices if devices is not None else jax.devices())
        dp = self.resolve_dp(len(devices))
        names = (AXIS_DATA, AXIS_PIPELINE, AXIS_SEQUENCE, AXIS_EXPERT,
                 AXIS_MODEL)
        sizes = (dp, self.pp, self.sp, self.ep, self.tp)
        total = int(np.prod(sizes))
        if total > len(devices):
            raise ValueError('ParallelSpec wants %d devices, have %d'
                             % (total, len(devices)))
        from autodist_tpu.parallel.mesh import device_mesh_array
        arr = device_mesh_array(sizes, devices, dcn_dp=self.dcn_dp)
        return Mesh(arr, names)


def mesh_axis_for(logical, rules, mesh):
    """Resolve one logical axis to a live mesh axis name (or None)."""
    for name, target in rules:
        if name == logical:
            if target is None or target not in mesh.shape:
                return None
            if mesh.shape[target] == 1:
                return None  # size-1 axis: sharding is a no-op; keep specs tidy
            return target
    return None


def spec_for_axes(axes, rules, mesh):
    """PartitionSpec for a tuple of logical axis names."""
    if axes is None:
        return P()
    used = set()
    out = []
    for logical in axes:
        target = mesh_axis_for(logical, rules, mesh)
        if target in used:
            target = None  # a mesh axis may shard only one tensor dim
        if target is not None:
            used.add(target)
        out.append(target)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shardings_for_tree(axes_tree, rules, mesh):
    """Map an axes-metadata pytree to NamedShardings.

    ``axes_tree`` mirrors the param tree but holds tuples of logical axis
    names (or None) at the leaves.
    """
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, spec_for_axes(axes, rules, mesh)),
        axes_tree,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and
                                        all(isinstance(a, (str, type(None)))
                                            for a in x)))


class _ShardingCtx(threading.local):
    def __init__(self):
        self.mesh = None
        self.rules = None
        self.manual_axes = ()   # mesh axes under shard_map (explicit mode)
        self.options = {}       # execution options (e.g. microbatches)


_CTX = _ShardingCtx()


def ctx_option(key, default=None):
    """Read an execution option installed by the active sharding_ctx."""
    return _CTX.options.get(key, default)


class sharding_ctx:
    """Context manager installing (mesh, rules) for :func:`constrain`.

    The Trainer enters this around tracing so model code can annotate
    activations by logical axes without threading the mesh through every
    call signature. ``manual_axes`` marks mesh axes the step runs manually
    (inside shard_map) — model code uses explicit collectives over those
    (e.g. ring attention over ``seq``) instead of sharding constraints.
    """

    def __init__(self, mesh, rules, manual_axes=(), options=None):
        self._new = (mesh, rules, tuple(manual_axes), options or {})
        self._old = None

    def __enter__(self):
        self._old = (_CTX.mesh, _CTX.rules, _CTX.manual_axes,
                     _CTX.options)
        (_CTX.mesh, _CTX.rules, _CTX.manual_axes,
         _CTX.options) = self._new
        return self

    def __exit__(self, *exc):
        (_CTX.mesh, _CTX.rules, _CTX.manual_axes,
         _CTX.options) = self._old


def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` as this package uses it: replication checking
    off (these regions mix manual collectives with replicated outputs)
    and ``axis_names`` naming the manual subset of the mesh axes (None =
    all of them, a fully manual region).

    A partial region also takes the mesh's size-1 axes along as manual,
    which is a no-op for the math: Mosaic refuses to lower a Pallas
    kernel while ANY mesh axis is still automatic, so a region whose
    named axes are all the size>1 ones must not leave the idle ones
    auto. ``mesh=None`` nests a region, manual over ``axis_names``,
    inside a manual one."""
    names = set(axis_names or ())
    if mesh is None:
        # a region nested in a manual one: under the mesh of the trace,
        # whose size-1 axes the outer region has taken already
        return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                             axis_names=names, check_vma=False)
    if names:
        names |= {a for a, n in mesh.shape.items() if n == 1}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=names,
                         check_vma=False)


def manual_axis(mesh_axis):
    """The live manual (shard_map) axis name, or None.

    Returns ``mesh_axis`` only when the current step executes that mesh
    axis manually AND its size exceeds 1."""
    return mesh_axis if mesh_axis in _CTX.manual_axes else None


def current_mesh():
    """The mesh installed by the active sharding_ctx (or None)."""
    return _CTX.mesh


def active_manual_axes():
    """Mesh axes the current trace runs manually (shard_map), if any."""
    return _CTX.manual_axes


def unsharded_execution():
    """True when the current trace computes on purely device-local data:
    no mesh, a single-device mesh, or every size>1 mesh axis manual
    (shard_map). This is the safety condition for invoking an opaque
    kernel (``pallas_call``) that GSPMD cannot partition — under
    automatic sharding XLA would all-gather its operands instead."""
    if _CTX.mesh is None:
        return True
    for name, size in _CTX.mesh.shape.items():
        if size > 1 and name not in _CTX.manual_axes:
            return False
    return True


def live_mesh_axis(logical):
    """Mesh axis a logical axis is currently bound to (size>1), or None.

    Lets modules pick sharding-aware algorithms (e.g. one-hot-matmul
    embedding lookup when the vocab dim is tensor-sharded)."""
    if _CTX.mesh is None:
        return None
    rules = _CTX.rules
    if rules is None:
        rules = [list(r) for r in DEFAULT_RULES]
    return mesh_axis_for(logical, rules, _CTX.mesh)


def constrain(x, axes, rules=None, mesh=None):
    """with_sharding_constraint by logical axes; no-op outside a ctx.

    Inside a partial-manual shard_map region, manual axes are stripped
    from the spec (they are positional there, not sharding annotations).
    """
    mesh = mesh if mesh is not None else _CTX.mesh
    if mesh is None:
        return x
    rules = rules if rules is not None else _CTX.rules
    if rules is None:
        rules = [list(r) for r in DEFAULT_RULES]
    spec = spec_for_axes(axes, rules, mesh)
    if _CTX.manual_axes:
        spec = P(*[None if a in _CTX.manual_axes else a for a in spec])
        while len(spec) and spec[-1] is None:
            spec = P(*spec[:-1])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
