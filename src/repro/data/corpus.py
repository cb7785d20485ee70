"""Device-resident client corpus: the FL data plane lives here.

``ClientCorpus`` holds the stacked per-client arrays (``x:(N,S,...)``,
``y:(N,S)``, ``w:(N,S)`` plus any extra keys) **on device, once, in their
natural dtype** — uint8 for real image ingest, float32 for the synthetic
corpus — and answers the three questions every layer above used to
re-derive per round:

* **data plane** — :meth:`cohort` is a jitted on-device gather along the
  client axis (optionally fused with the dtype :class:`Normalize` and a
  :class:`DataQueue` activity mask), replacing the host-side
  ``{k: v[idx]}`` slice + full-cohort H2D transfer the seed-era ``Server``
  performed every round. Per round, only the ``idx`` (and optional queue
  counts) cross the host→device boundary.
* **control plane** — :meth:`label_histograms` / :meth:`label_entropy` /
  :meth:`sizes` are the per-client stats selectors grouped and ranked on
  (previously recomputed by each selector's ``bind_data`` hook).
* **placement** — :meth:`shard` lays the client axis out over a 1-D
  ``("clients",)`` mesh with a ``NamedSharding`` exactly once; subsequent
  cohort gathers run as SPMD programs over the sharded operand and land
  already distributed for the ``shard_map`` client fan-out. Uneven
  client counts (``N % mesh != 0`` — the paper's N=100 on any realistic
  accelerator count) are a first-class *padded-shard* layout: the client
  axis is padded with zero rows (zero ``w`` ⇒ inert clients, tracked by
  :attr:`client_valid`) up to the next mesh multiple, so every array
  shards ``P("clients")`` instead of silently replicating. The padding
  is data-plane only — :attr:`num_clients`, :meth:`sizes`,
  :meth:`label_histograms`, :meth:`label_entropy` and :meth:`as_numpy`
  all keep reporting the *real* N, global client ids in :meth:`cohort`
  are unchanged (padding appends, so the id map is the identity), and
  :meth:`signature` keys compiled programs on the padded layout.

uint8 images are 4x smaller resident than the float32 corpus they
replace; normalization happens inside the traced gather, so the float32
cohort exists only at |S_t| scale on the accelerator, never at N scale
and never on the host.

``DataQueue`` is the round-indexed subset schedule behind the
entropy-driven dynamic-data-queue selector (arXiv 2410.17792): each
client's *effective* local dataset starts small and grows to the full
shard over training; the corpus applies it as a weight mask inside the
same jitted gather, so schedules never re-materialize data.
"""
from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

CLIENT_AXIS = "clients"


@dataclass(frozen=True)
class Normalize:
    """On-device dtype policy: ``(x * scale - mean) / std`` in float32.

    The identity transform is ``Normalize()``; real uint8 ingest pairs
    ``scale=1/255`` with per-channel dataset statistics (see
    :func:`repro.data.ingest.cifar10_normalizer`). Applied inside the
    jitted cohort gather — the corpus stays in its storage dtype.
    """
    scale: float = 1.0
    mean: tuple = (0.0,)
    std: tuple = (1.0,)

    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(jnp.float32) * jnp.float32(self.scale)
        mean = jnp.asarray(self.mean, jnp.float32)
        std = jnp.asarray(self.std, jnp.float32)
        return (x - mean) / std


@dataclass(frozen=True)
class DataQueue:
    """Round-indexed per-client effective-dataset schedule.

    ``active(round, sizes)`` maps each client's real sample count to the
    number of samples "released" to it at that round: a fraction ramping
    from ``start_frac`` to 1.0 over ``rounds_to_full`` rounds, either
    continuously (``growth="linear"``) or in ``stages`` discrete steps
    (``growth="staged"`` — the dynamic data queue of arXiv 2410.17792,
    where clients graduate between queue levels). Deterministic in
    (round, sizes): a speculative selector copy reproduces the exact
    schedule, so queue-masked dispatches replay bit-for-bit.
    """
    start_frac: float = 0.25
    rounds_to_full: int = 100
    growth: str = "linear"          # "linear" | "staged"
    stages: int = 4
    min_samples: int = 1

    def __post_init__(self):
        if self.growth not in ("linear", "staged"):
            raise ValueError(
                f"DataQueue growth must be 'linear' or 'staged', "
                f"got {self.growth!r}")

    def frac(self, round_idx: int) -> float:
        t = min(max(round_idx, 0) / max(self.rounds_to_full, 1), 1.0)
        if self.growth == "staged":
            # graduate in `stages` equal steps; final stage is the full set
            step = np.ceil(t * self.stages) / self.stages
            t = float(step)
        return float(self.start_frac + (1.0 - self.start_frac) * t)

    def active(self, round_idx: int, sizes: np.ndarray) -> np.ndarray:
        sizes = np.asarray(sizes, np.int64)
        want = np.ceil(self.frac(round_idx) * sizes).astype(np.int64)
        return np.clip(np.maximum(want, self.min_samples), 0, sizes)


def _as_device(v):
    """Host array -> committed device array, dtype preserved."""
    if isinstance(v, jax.Array):
        return v
    return jnp.asarray(v)


def pad_client_axis(arrays: dict, pad: int) -> dict:
    """Append ``pad`` zero rows to every array's client axis.

    Zero rows (rather than edge repeats) make padded clients provably
    inert: their ``w`` mask is all-zero, so even a stray gather of a
    padded id contributes nothing to any weighted reduction. Real rows
    are untouched — global client ids keep their positions.
    """
    if pad <= 0:
        return dict(arrays)
    return {k: jnp.concatenate(
        [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)], axis=0)
        for k, v in arrays.items()}


class ClientCorpus(Mapping):
    """Stacked client arrays resident on device; see the module docstring.

    Implements ``Mapping`` over its arrays so seed-era call sites that
    treated the corpus as a plain ``{"x": ..., "y": ..., "w": ...}`` dict
    (shape probes, signature keys) keep working unchanged.
    """

    plane = "resident"

    def __init__(self, arrays: dict, *, transform: Normalize | None = None):
        if not arrays:
            raise ValueError("ClientCorpus needs at least one array")
        n = {k: np.shape(v)[0] for k, v in arrays.items()}
        if len(set(n.values())) != 1:
            raise ValueError(f"client axes disagree: {n}")
        self._arrays = {k: _as_device(v) for k, v in arrays.items()}
        self.transform = transform
        self._mesh = None
        self._n = int(next(iter(self._arrays.values())).shape[0])  # real N
        self._pad = 0                   # zero rows appended by shard()
        self._hists: dict = {}          # num_classes (or None) -> (N, C)
        self._sizes: np.ndarray | None = None
        self._gather = jax.jit(self.cohort_gather)
        self._gather_queued = jax.jit(self._gather_queued_impl)

    # ------------------------------------------------------- constructors
    @classmethod
    def from_stacked(cls, data, *, transform: Normalize | None = None
                     ) -> "ClientCorpus":
        """Wrap a ``stack_clients``-style dict; identity on a corpus."""
        if isinstance(data, ClientCorpus):
            return data
        return cls(dict(data), transform=transform)

    @classmethod
    def from_parts(cls, x, y, parts, *, batch_multiple: int = 1,
                   transform: Normalize | None = None) -> "ClientCorpus":
        """Partition assignment lists -> stacked, device-resident corpus.

        Unlike ``stack_clients`` (which casts nothing), the stacked ``x``
        keeps ``x.dtype`` — hand in uint8 images and a :class:`Normalize`
        and the resident corpus is 4x smaller than the float32 layout.
        """
        from .partition import stack_clients
        return cls(stack_clients(x, y, parts, batch_multiple),
                   transform=transform)

    # ---------------------------------------------------- Mapping protocol
    def __getitem__(self, key):
        return self._arrays[key]

    def __iter__(self):
        return iter(self._arrays)

    def __len__(self):
        return len(self._arrays)

    # ----------------------------------------------------------- metadata
    @property
    def num_clients(self) -> int:
        """The *real* client count N — control-plane surfaces never see
        the padded rows :meth:`shard` may have appended."""
        return self._n

    @property
    def padded_num_clients(self) -> int:
        """Leading-axis length of the resident arrays (N + shard pad)."""
        return int(next(iter(self._arrays.values())).shape[0])

    @property
    def client_valid(self) -> np.ndarray:
        """(padded_N,) bool — True for real clients, False for pad rows."""
        valid = np.zeros(self.padded_num_clients, bool)
        valid[:self._n] = True
        return valid

    @property
    def samples_per_client(self) -> int:
        return int(self._arrays["y"].shape[1]) if "y" in self._arrays \
            else int(next(iter(self._arrays.values())).shape[1])

    def signature(self) -> tuple:
        """Hashable (key, shape, dtype) + transform + pad tuple for jit
        caches — a padded-shard layout must never be served a program
        compiled for the unpadded (or differently padded) one."""
        return (tuple((k, tuple(v.shape), str(v.dtype))
                      for k, v in sorted(self._arrays.items())),
                self.transform, self._pad)

    @property
    def nbytes(self) -> int:
        """Resident bytes of the stored corpus (storage dtype), summed
        over every device shard (pad rows included)."""
        return int(sum(v.size * v.dtype.itemsize
                       for v in self._arrays.values()))

    def device_nbytes(self) -> int:
        """Max resident bytes of the corpus on any one addressable device.

        Replicated layouts hold the whole corpus per device (== ``nbytes``
        for a single-device or replicated placement); the padded-shard
        layout holds ~``nbytes / mesh`` — the memory win the uneven-mesh
        A/B in benchmarks/dataplane_bench.py measures.
        """
        per: dict = {}
        for v in self._arrays.values():
            for s in v.addressable_shards:
                per[s.device] = per.get(s.device, 0) + int(
                    s.data.size * s.data.dtype.itemsize)
        return max(per.values())

    def cohort_nbytes(self, m: int) -> int:
        """Bytes a host-slice data plane would ship per round for a cohort
        of ``m`` clients — the float32 post-transform layout the seed-era
        server transferred (the corpus path ships only ``idx``)."""
        total = 0
        for k, v in self._arrays.items():
            itemsize = (4 if k == "x" and self.transform is not None
                        else v.dtype.itemsize)
            total += int(np.prod(v.shape[1:], dtype=np.int64)) * itemsize * m
        return total

    def as_numpy(self) -> dict:
        """Host copy of the raw (untransformed) arrays, storage dtype,
        real N rows only (shard pad rows are a placement detail)."""
        return {k: np.asarray(v)[:self._n] for k, v in self._arrays.items()}

    def memory_report(self) -> dict:
        """Plane-aware byte accounting, same schema as the streaming
        plane's (:meth:`repro.data.stream.HostCorpus.memory_report`):
        the resident plane keeps the whole corpus on device and holds no
        host mapping or staging buffers."""
        return {
            "plane": self.plane,
            "host_mapped_bytes": 0,
            "host_is_mmap": False,
            "device_resident_bytes": self.device_nbytes(),
            "staging_nbytes": 0,
            "num_clients": self._n,
        }

    # ------------------------------------------------- control-plane stats
    def sizes(self) -> np.ndarray:
        """Per-client real (unpadded) sample counts, from the w mask."""
        if self._sizes is None:
            if "w" in self._arrays:
                self._sizes = np.asarray(
                    jnp.sum(self._arrays["w"][:self._n], axis=1)
                ).astype(np.int64)
            else:
                self._sizes = np.full(self.num_clients,
                                      self.samples_per_client, np.int64)
        return self._sizes

    def label_histograms(self, num_classes: int | None = None) -> np.ndarray:
        """(N, C) weighted label counts — the grouping/ranking input for
        ``catgroups`` and the ``queue`` selector; computed once per
        ``num_classes``, host-side (control plane), cached. Always real-N
        rows, whatever the resident padding."""
        if num_classes not in self._hists:
            from ..core.pools import label_histograms
            y = np.asarray(self._arrays["y"])[:self._n]
            w = (np.asarray(self._arrays["w"])[:self._n]
                 if "w" in self._arrays else None)
            self._hists[num_classes] = label_histograms(
                y, w, num_classes=num_classes)
        return self._hists[num_classes]

    def label_entropy(self) -> np.ndarray:
        """Per-client Shannon entropy (nats) of the label distribution."""
        from ..core.pools import hist_entropy
        hists = self.label_histograms()
        return np.asarray([hist_entropy(h) for h in hists], np.float64)

    # ------------------------------------------------------------ placement
    def shard(self, mesh, axis: str = CLIENT_AXIS) -> "ClientCorpus":
        """Lay the client axis over ``mesh[axis]`` once (idempotent).

        ``N % mesh[axis] != 0`` is a first-class layout, not a fallback:
        the client axis is padded with zero rows (:func:`pad_client_axis`)
        up to the next mesh multiple, so every array shards ``P(axis)``
        on any mesh size — never replicates. Padding appends, so global
        client ids are unchanged and :meth:`cohort` needs no id remap;
        padded clients carry zero weight and are excluded from every
        control-plane stat (real-N contract). Re-sharding onto a mesh of
        a different size re-derives the pad from the real rows. Returns
        self.
        """
        if self._mesh is mesh:
            return self
        from jax.sharding import NamedSharding, PartitionSpec as P
        size = mesh.shape[axis]
        pad = (-self._n) % size
        if pad != self._pad:
            real = {k: v[:self._n] for k, v in self._arrays.items()}
            self._arrays = pad_client_axis(real, pad)
            self._pad = pad
        sharding = NamedSharding(mesh, P(axis))
        for k, v in self._arrays.items():
            self._arrays[k] = jax.device_put(v, sharding)
        self._mesh = mesh
        return self

    # ------------------------------------------------------------ data plane
    def put_index(self, v) -> jax.Array:
        """Host index vector -> device, replicated over the corpus mesh.

        Once the corpus is mesh-sharded, a single-device ``idx`` would be
        resharded device-to-device inside the jitted gather on every call;
        placing it replicated up front keeps the gather free of implicit
        transfers (and visible as the only H2D payload per round). This is
        how a caller pre-stages ``idx`` to prove the gather transfer-free
        under ``jax.transfer_guard`` on any mesh size."""
        if self._mesh is None:
            return jnp.asarray(v)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(v, NamedSharding(self._mesh, P()))

    def cohort_gather(self, arrays: dict, idx: jax.Array) -> dict:
        """The gather's traced body; jitted, its program is named
        ``jit_cohort_gather`` on a trace."""
        out = {k: v[idx] for k, v in arrays.items()}
        if self.transform is not None and "x" in out:
            out["x"] = self.transform(out["x"])
        return out

    def _gather_queued_impl(self, arrays: dict, idx: jax.Array,
                            active: jax.Array) -> dict:
        out = self.cohort_gather(arrays, idx)
        if "w" in out:
            s = out["w"].shape[1]
            live = jnp.arange(s)[None, :] < active[:, None]
            out["w"] = out["w"] * live.astype(out["w"].dtype)
        return out

    def traced_cohort(self, idx: jax.Array, active=None) -> dict:
        """The cohort gather as a *traceable* op, for callers composing it
        into a larger jitted program (the scan engine folds R rounds of
        gather + ClientUpdate + judgment into one ``lax.scan``). Same math
        as :meth:`cohort` — ``idx`` must already be a traced/device array;
        the streaming plane deliberately has no such method (its gather is
        host-side), which is how engines detect a foldable data plane."""
        if active is None:
            return self.cohort_gather(self._arrays, idx)
        return self._gather_queued_impl(self._arrays, idx, active)

    def cohort(self, idx, active=None) -> dict:
        """Jitted on-device gather of clients ``idx`` along axis 0.

        ``active`` (optional, per-selected-client sample counts from a
        :class:`DataQueue`) masks each client's weight row down to its
        released prefix — inside the same traced program, so a dynamic
        queue costs no extra transfer or copy. Only ``idx`` (and
        ``active``) move host→device; an already-device ``idx`` is used
        as-is, making the gather provably transfer-free (see
        benchmarks/dataplane_bench.py's tripwire). ``idx`` holds *global*
        client ids in ``[0, N)`` — the padded-shard layout appends its pad
        rows, so the id map through the padded operand is the identity
        and the gather stays SPMD on any mesh size.
        """
        if not isinstance(idx, jax.Array):
            idx = self.put_index(np.asarray(idx, np.int32))
        if active is None:
            return self._gather(self._arrays, idx)
        if not isinstance(active, jax.Array):
            active = self.put_index(np.asarray(active, np.int32))
        return self._gather_queued(self._arrays, idx, active)
