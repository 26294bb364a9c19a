"""Device-mesh management — the framework's ICI/DCN substrate.

TPU-native replacement for the reference's collective-group machinery
(``python/ray/util/collective/collective.py:40-151`` — named NCCL/Gloo groups
over actors; ``nccl_collective_group.py:128`` allreduce): instead of explicit
collective calls between actors, the framework lays models out over a
``jax.sharding.Mesh`` and lets XLA insert ``psum``/``all_gather``/
``reduce_scatter`` over ICI under ``jit`` (SURVEY.md §2.4 translation table).

Axes (logical → physical):
- ``dp``: data/replica parallelism (the reference's replica scaling axis)
- ``tp``: tensor parallelism (BASELINE config 4: Llama TP=4 over ICI)
- ``sp``: sequence/context parallelism for long inputs (ring attention)

Multi-host (DCN) boot mirrors the reference's group bootstrap: JAX's
distributed runtime plays the GCS-address role (SURVEY.md §2.4).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ray_dynamic_batching_tpu.models.base import ServableModel, param_path_specs
from ray_dynamic_batching_tpu.utils.logging import get_logger

logger = get_logger("mesh")

AXIS_ORDER = ("dp", "pp", "sp", "tp", "ep")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Axis sizes for the five-way parallelism mesh.

    dp = data/replica, pp = pipeline stages, sp = sequence (ring attention),
    tp = tensor, ep = expert (MoE). Axes default to 1 (inactive)."""

    dp: int = 1
    sp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1

    @property
    def n_devices(self) -> int:
        return self.dp * self.pp * self.sp * self.tp * self.ep

    @staticmethod
    def auto(n_devices: int, tp: Optional[int] = None, sp: int = 1) -> "MeshConfig":
        """Pick dp x sp x tp for a device count: prefer TP up to 4 (one ICI
        hop on v5e trays), data-parallel beyond."""
        if n_devices % sp != 0:
            raise ValueError(f"sp={sp} does not divide {n_devices} devices")
        if tp is None:
            tp = 1
            for cand in (4, 2):
                if n_devices % (cand * sp) == 0:
                    tp = cand
                    break
        if n_devices % (tp * sp) != 0:
            raise ValueError(
                f"tp={tp} x sp={sp} does not divide {n_devices} devices"
            )
        return MeshConfig(dp=n_devices // (tp * sp), sp=sp, tp=tp)


def build_mesh(
    config: MeshConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    n = config.n_devices
    if len(devices) < n:
        raise ValueError(
            f"mesh needs {n} devices (dp={config.dp} pp={config.pp} "
            f"sp={config.sp} tp={config.tp} ep={config.ep}) but only "
            f"{len(devices)} available"
        )
    arr = np.array(devices[:n]).reshape(
        config.dp, config.pp, config.sp, config.tp, config.ep
    )
    return Mesh(arr, AXIS_ORDER)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    devices = [device] if device is not None else jax.devices()[:1]
    return Mesh(np.array(devices).reshape(1, 1, 1, 1, 1), AXIS_ORDER)


# --- sharding helpers -----------------------------------------------------

def _feasible_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes that don't divide the corresponding dim (e.g. GQA with
    kv_heads < tp replicates the kv projections instead of erroring), and
    trailing Nones: ``P(None, 'tp', None) != P(None, 'tp')`` to jit's
    executable cache although they place data identically, and programs
    hand arrays back under the trimmed spelling — a cache allocated under
    the long one makes the first program warmed compile a second time on
    the first live dispatch."""
    out = []
    for i, ax in enumerate(spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if i < len(shape) and shape[i] % size == 0:
            out.append(ax)
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def feasible_sharding(mesh: Mesh, spec: P,
                      shape: Tuple[int, ...]) -> NamedSharding:
    """``spec`` on ``mesh`` for an array of ``shape``, with the axes that
    do not divide it replicated (:func:`_feasible_spec`)."""
    return NamedSharding(mesh, _feasible_spec(spec, shape, mesh))


def param_shardings(mesh: Mesh, model: ServableModel, params: Any) -> Any:
    """NamedShardings for every param leaf from the model's sharding rules
    (infeasible axes degrade to replication rather than erroring)."""
    specs = param_path_specs(model, params)
    return jax.tree_util.tree_map(
        lambda leaf, s: feasible_sharding(mesh, s, leaf.shape),
        params,
        specs,
    )


def shard_params(mesh: Mesh, model: ServableModel, params: Any) -> Any:
    """Place params on the mesh per the model's rules (TP weights split over
    the tp axis, everything else replicated)."""
    shardings = param_shardings(mesh, model, params)
    return jax.device_put(params, shardings)


def _sharded_alloc(mesh: Mesh, make_fn, spec) -> Any:
    """Allocate a cache pytree DIRECTLY onto the mesh per its pspec
    dataclass. The buffers never materialize unsharded on any single
    device — a pool sized to fit only when split over the tp chips must
    not OOM chip 0 on the way in."""
    import dataclasses

    shapes = jax.eval_shape(make_fn)

    def _shard(field_spec, field_shape):
        if field_shape is None:  # absent optional plane (e.g. scales)
            return None
        return feasible_sharding(mesh, field_spec, field_shape.shape)

    # Field-generic so every cache plane — including a quantized cache's
    # scale planes — gets a sharding; a hand-listed constructor here
    # silently dropped new planes once already.
    shardings = type(shapes)(**{
        f.name: _shard(getattr(spec, f.name, None), getattr(shapes, f.name))
        for f in dataclasses.fields(shapes)
    })
    return jax.jit(make_fn, out_shardings=shardings)()  # rdb-lint: disable=jit-retrace-hazard (one-shot cache allocation at engine construction — jit only carries out_shardings so GSPMD places the buffers; never called on the serving path)


def make_sharded_paged_cache(
    mesh: Mesh, model: Any, num_slots: int, num_pages: int,
    page_size: int, max_len: int,
) -> Any:
    """Allocate a model's PAGED KV pool onto the mesh per its
    ``paged_cache_pspec`` (ROADMAP item 2): page planes split on the
    kv-head dim, page table + lengths replicated —
    page indices are shard-invariant, so the host-side free-list
    allocator stays replica-global and untouched."""
    return _sharded_alloc(
        mesh,
        lambda: model.make_paged_cache(
            num_slots, num_pages, page_size, max_len,
            tp=int(mesh.shape.get("tp", 1)),
        ),
        model.paged_cache_pspec(),
    )


def replicate(mesh: Mesh, tree: Any) -> Any:
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def batch_sharding(mesh: Mesh, extra_dims: int = 1) -> NamedSharding:
    """Shard the leading batch axis over dp; remaining dims replicated."""
    return NamedSharding(mesh, P("dp", *([None] * extra_dims)))


def seq_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    """[B, T, ...] with batch over dp and sequence over sp (long-context)."""
    return NamedSharding(mesh, P("dp", "sp", *([None] * extra_dims)))


# --- multi-host boot (DCN) ------------------------------------------------

def multihost_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> Dict[str, int]:
    """Initialize JAX's distributed runtime across hosts (DCN). The
    coordinator plays the role the reference's GCS address plays for
    collective-group bootstrap (SURVEY.md §2.4). No-op when single-process.
    """
    if num_processes is None or num_processes <= 1:
        return {"process_index": 0, "process_count": 1}
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }
