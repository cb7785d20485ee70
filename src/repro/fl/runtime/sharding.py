"""Mesh-sharded client fan-out for the runtime engine.

``Server`` runs the vmapped ClientUpdate for the whole cohort on one
device. Here the stacked client axis is instead partitioned across a
1-D ``("clients",)`` device mesh with ``shard_map``: each device vmaps
over its local shard of the cohort, no collectives needed (clients are
independent until aggregation, which stays in the engine). The cohort is
padded up to a multiple of the mesh size by repeating the last client
row, and the padded outputs are sliced off before judgment so verdicts
and aggregation see exactly |S_t| clients — both the pad and the slice
happen *inside* the one jitted program, so an uneven cohort pays no
per-round eager ``repeat``/``concatenate`` dispatches.

Cohort padding composes with the corpus's padded-shard layout
(:meth:`repro.data.corpus.ClientCorpus.shard`): the corpus pads the
*resident* client axis so an uneven N shards ``P("clients")``, while
this module pads the *gathered cohort* so an uneven |S_t| shard_maps —
two independent axes of the same uneven-mesh contract.

``make_client_mesh`` builds the 1-D mesh over whatever devices exist —
on a TPU slice that is the whole pod; reuse ``launch.mesh`` for 2-D
production meshes and pass ``mesh_axis_size`` devices explicitly.

The axis name is shared with :mod:`repro.data.corpus`: a ``ClientCorpus``
sharded over the same ``("clients",)`` mesh feeds its on-device cohort
gathers straight into this fan-out with no resharding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ...core.strategies import ApplyFn
from ...data.corpus import CLIENT_AXIS
from ..server import _make_client_fn

__all__ = [
    "CLIENT_AXIS", "client_mesh_from", "make_client_mesh",
    "make_sharded_client_fn", "pad_to_multiple",
]


def make_client_mesh(devices=None) -> Mesh:
    """1-D mesh over ``devices`` (default: all) with a "clients" axis."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devs), (CLIENT_AXIS,))


def client_mesh_from(mesh: Mesh) -> Mesh:
    """Client mesh over a production mesh's client rows.

    ``launch.mesh`` maps one FL client group per ("pod", "data") row
    (``fl_clients_for``); this takes the first device of each row — the
    weights-level ClientUpdate is small enough to live on one chip, the
    row's "model" axis stays free for model-parallel apply_fns."""
    from ...launch.mesh import fl_clients_for
    rows = fl_clients_for(mesh)
    devs = mesh.devices.reshape(rows, -1)[:, 0]
    return Mesh(devs, (CLIENT_AXIS,))


def pad_to_multiple(tree, multiple: int):
    """Edge-repeat every leaf's leading axis up to a multiple; identity if
    already divisible. Padded rows are dropped by the caller post-hoc, so
    repeating real rows keeps every traced op well-conditioned."""
    def pad(x):
        n = x.shape[0]
        rem = (-n) % multiple
        if rem == 0:
            return x
        reps = jnp.repeat(x[-1:], rem, axis=0)
        return jnp.concatenate([x, reps], axis=0)
    return jax.tree.map(pad, tree)


def make_sharded_client_fn(apply_fn: ApplyFn, spec, in_axes, mesh: Mesh,
                           *, donate_data: bool = True, inner=None,
                           inner_axes: tuple = (0,)):
    """shard_map'd + jitted ClientUpdate over the ("clients",) mesh axis.

    Returns ``fn(global_params, data, prev_p, c_loc, c_glob, ...)`` with
    the same signature/semantics as ``Server._client_fn()`` — including the
    leading-axis length of the result (padding is internal). ``in_axes``
    is the strategy's vmap spec; axis-0 arguments shard over the mesh,
    None arguments replicate.

    ``inner`` swaps the vmapped default for a strategy-built fn.
    ``inner_axes`` are the vmap axes of any arguments the inner fn takes
    *beyond* the standard five — the default ``(0,)`` is the FedCAT chain
    contract (one extra axis-0 chain-validity mask; the inner fn's
    leading axis is then the GROUP axis: whole chains shard onto devices,
    never individual chain stages, and mesh padding repeats whole groups
    whose (dropped) outputs cannot leak into real chains); strategies
    whose ``make_client_fn`` keeps the plain five-argument client
    signature (the LM window rule) pass ``()``.
    """
    vm = inner if inner is not None else _make_client_fn(apply_fn, spec,
                                                         in_axes)
    axes = tuple(in_axes) + (tuple(inner_axes) if inner is not None
                             else ())
    n = mesh.shape[CLIENT_AXIS]
    in_specs = tuple(P(CLIENT_AXIS) if ax == 0 else P() for ax in axes)
    mapped = jax.shard_map(vm, mesh=mesh, in_specs=in_specs,
                           out_specs=P(CLIENT_AXIS), check_vma=False)

    def padded_call(global_params, data, *rest):
        # pad-to-mesh and slice-back are traced: shapes are static under
        # jit, so an uneven cohort costs zero eager dispatches per round
        # (the pad/slice fuse into the compiled program)
        m = jax.tree.leaves(data)[0].shape[0]
        args = (global_params, data) + rest
        padded = tuple(
            pad_to_multiple(a, n) if ax == 0 and a is not None else a
            for a, ax in zip(args, axes))
        out = mapped(*padded)
        if jax.tree.leaves(out)[0].shape[0] == m:
            return out
        return jax.tree.map(lambda x: x[:m], out)

    # the per-round data slices are fresh buffers — donating them lets XLA
    # reuse cohort-sized memory across pipelined rounds (no-op on CPU,
    # which cannot alias donated inputs and would warn every compile)
    donate_data = donate_data and jax.default_backend() != "cpu"
    return jax.jit(padded_call, donate_argnums=(1,) if donate_data else ())
