"""Device mesh construction.

The reference resolves abstract device strings to TF device strings and
lets TF's placer handle the rest (``autodist/kernel/device/resolver.py:
47-67``). The TPU-native equivalent builds a ``jax.sharding.Mesh`` whose
axes the strategy compiler binds shardings onto; XLA then handles placement
and collective lowering over ICI/DCN.

Axes follow :data:`autodist_tpu.const.ALL_AXES`:

``data``  — replica axis (the only axis the reference has),
``model`` — tensor parallelism, ``pipe`` — pipeline stages,
``seq``   — sequence/context parallelism (ring attention / Ulysses),
``expert``— MoE expert parallelism.
"""
import numpy as np

import jax
from jax.sharding import Mesh

from autodist_tpu.const import (ALL_AXES, AXIS_DATA)
from autodist_tpu.utils import logging


def device_mesh_array(sizes, devices, dcn_dp=1):
    """Topology-aware device placement for a mesh of shape ``sizes``.

    - ``dcn_dp > 1`` (multi-slice): the leading (data) axis is split
      ``dcn_dp``-ways across slices so data-parallel gradient reduction
      is the only traffic that crosses DCN; all other axes stay inside
      a slice on ICI (the scaling-book hierarchy rule). On real
      multi-slice TPU (devices carry ``slice_index``) this uses
      ``mesh_utils.create_hybrid_device_mesh``; elsewhere contiguous
      device groups emulate slices so the layout is testable on a
      virtual CPU mesh.
    - single-slice TPU: ``mesh_utils.create_device_mesh`` picks an
      ICI-neighbor-aware ordering (e.g. ring orders on a torus).
    - anything else (CPU/virtual): plain row-major reshape, keeping the
      deterministic device order the numeric-parity tests rely on.
    """
    sizes = [int(s) for s in sizes]
    n = int(np.prod(sizes))
    devices = list(devices)[:n]
    if dcn_dp > 1:
        if sizes[0] % dcn_dp:
            raise ValueError(
                'dcn_dp=%d must divide the data axis (%d)'
                % (dcn_dp, sizes[0]))
        ici_shape = [sizes[0] // dcn_dp] + sizes[1:]
        dcn_shape = [dcn_dp] + [1] * (len(sizes) - 1)
        slice_ids = {getattr(d, 'slice_index', None) for d in devices}
        if None not in slice_ids:
            # real multi-slice hardware: the slice structure must match,
            # else the emulation below would silently straddle physical
            # DCN boundaries with ICI axes — the exact layout this knob
            # exists to prevent
            if len(slice_ids) != dcn_dp:
                raise ValueError(
                    'dcn_dp=%d but the %d devices span %d slices'
                    % (dcn_dp, len(devices), len(slice_ids)))
            from jax.experimental import mesh_utils
            return mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices)
        groups = np.array(devices).reshape(dcn_dp, n // dcn_dp)
        subs = [device_mesh_array(ici_shape, list(g)) for g in groups]
        return np.stack(subs).reshape(sizes)
    if len(devices) > 1 and all(d.platform == 'tpu' for d in devices):
        # a failure here (e.g. a device subset that is not a cuboid of
        # the slice) is an error: a silent row-major order would run,
        # but over the wrong ICI neighbours
        from jax.experimental import mesh_utils
        return mesh_utils.create_device_mesh(sizes, devices)
    return np.array(devices).reshape(sizes)


def data_axis_node_groups(mesh, forced_nodes=0):
    """Node groups over the DATA axis for two-level collective
    schedules: ``[[positions of node 0], [positions of node 1], ...]``
    or None when the mesh is effectively single-node (flat stays the
    emission — the degenerate case).

    Grouping keys, in order of authority:

    - ``forced_nodes >= 2`` (the ``AUTODIST_HIERARCHY_NODES``
      override): that many CONTIGUOUS equal groups — how a virtual CPU
      mesh or a dcn_dp layout (slice-major data axis) expresses its
      node structure for tests and benches;
    - real multi-slice TPU: the device's ``slice_index``;
    - multi-host SPMD: the device's ``process_index``.

    Groups must partition the axis into equal sizes >= 2 (the
    two-level schedule needs a real intra phase and a real inter
    phase); anything else returns None. Deterministic for a fixed
    mesh, so every SPMD process traces the same group layout.
    """
    if AXIS_DATA not in mesh.axis_names:
        return None
    n = mesh.shape[AXIS_DATA]
    if n <= 1:
        return None
    ax = list(mesh.axis_names).index(AXIS_DATA)
    # one representative device per data-axis position (index 0 on
    # every other axis)
    arr = np.moveaxis(mesh.devices, ax, 0)
    lane = arr.reshape(n, -1)[:, 0]
    if forced_nodes and forced_nodes >= 2:
        if n % forced_nodes or n // forced_nodes < 2:
            logging.warning(
                'AUTODIST_HIERARCHY_NODES=%d does not split the %d-way '
                'data axis into equal groups of >= 2; hierarchical '
                'emission stays flat', forced_nodes, n)
            return None
        g = n // forced_nodes
        return [list(range(i * g, (i + 1) * g))
                for i in range(forced_nodes)]
    keys = [getattr(d, 'slice_index', None) for d in lane]
    if any(k is None for k in keys):
        keys = [getattr(d, 'process_index', 0) for d in lane]
    groups = {}
    for pos, key in enumerate(keys):
        groups.setdefault(key, []).append(pos)
    out = [groups[k] for k in sorted(groups)]
    sizes = {len(g) for g in out}
    if len(out) < 2 or len(sizes) != 1 or sizes == {1}:
        return None
    return out


def build_mesh(num_replicas=None, axis_sizes=None, devices=None,
               dcn_dp=1):
    """Build the framework mesh.

    Args:
        num_replicas: size of the ``data`` axis when no explicit
            ``axis_sizes`` is given. Defaults to all visible devices.
        axis_sizes: ordered dict-like {axis_name: size}; their product must
            divide the available device count. Axes of size 1 are kept so
            strategies can always reference the full axis set.
        devices: explicit device list (defaults to ``jax.devices()``).
        dcn_dp: multi-slice factor — split the data axis this many ways
            across slice (DCN) boundaries; see :func:`device_mesh_array`.

    Returns:
        jax.sharding.Mesh
    """
    devices = list(devices if devices is not None else jax.devices())
    if axis_sizes:
        names = [a for a in ALL_AXES if a in axis_sizes]
        # preserve any user-defined extra axes in given order
        names += [a for a in axis_sizes if a not in names]
        sizes = [int(axis_sizes[a]) for a in names]
        if dcn_dp > 1 and (not names or names[0] != AXIS_DATA):
            raise ValueError(
                'dcn_dp requires a leading data axis (got %s) — only the '
                'data axis may cross slice boundaries' % (names,))
    else:
        n = num_replicas if num_replicas else len(devices)
        names, sizes = [AXIS_DATA], [int(n)]
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(
            'Mesh wants %d devices (%s) but only %d are visible' %
            (total, dict(zip(names, sizes)), len(devices)))
    if total < len(devices):
        logging.debug('Using %d of %d visible devices for the mesh',
                      total, len(devices))
    arr = device_mesh_array(sizes, devices, dcn_dp=dcn_dp)
    return Mesh(arr, tuple(names))


def mesh_from_strategy(strategy, resource_spec=None, devices=None):
    """Mesh for a compiled reference-style strategy: 1-D ``data`` axis sized
    by the replica list, optionally extended by resource-spec mesh hints.
    A ``dcn`` hint is the multi-slice factor (data axis split over DCN),
    not a mesh axis of its own."""
    hints = dict(resource_spec.mesh_hint) if resource_spec is not None \
        else {}
    dcn_dp = int(hints.pop('dcn', 1) or 1)
    devices = list(devices if devices is not None else jax.devices())
    n_replicas = len(strategy.graph_config.replicas) or len(devices)
    n_replicas = min(n_replicas, len(devices))
    if hints:
        hints.setdefault(AXIS_DATA, n_replicas)
        return build_mesh(axis_sizes=hints, devices=devices,
                          dcn_dp=dcn_dp)
    return build_mesh(num_replicas=n_replicas, devices=devices,
                      dcn_dp=dcn_dp)
