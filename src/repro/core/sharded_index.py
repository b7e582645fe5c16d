"""ShardedDircIndex — multi-macro DIRC-RAG retrieval with incremental updates.

The paper's system is sixteen DIRC cores inside ONE macro (handled by
`topk.hierarchical_topk`); scaling past a single macro means replicating the
whole macro and splitting the corpus across macros. This module models that
outer level:

  shard s  <->  one DIRC macro: its own per-document (per-"row") quantization
                scales, two's-complement bit-plane image, D-Sum LUT and
                integer-norm ReRAM buffer. All shard images are stacked on a
                leading axis, e.g. planes (n_shards, capacity, bits, dim),
                so shard-parallel scoring is a `vmap` (or `lax.map` /
                `shard_map`) over axis 0 — the QS dataflow per macro is
                unchanged: the query is broadcast (query-stationary), the
                documents never move.

Top-k is a three-level comparator tree: per-core local top-k and per-macro
merge via the existing `hierarchical_topk` (paper Fig. 3a), then a cross-
macro global comparator that sorts the tiny candidate list by
(-score, doc_id) — exactly `jax.lax.top_k`'s lower-index tie-break, so a
sharded search equals a monolithic `DircRagIndex.search` up to fp reduction
order (bit-exact on the integer paths).

Incremental updates (the corpus is no longer build-once):
  * `add_docs` appends each new document to the least-loaded shard, writing
    its codes/planes/LUT/norm into a free slot (capacity doubles by padding
    every shard image when the macro set is full);
  * `delete_docs` clears the slot's `alive` bit — a TOMBSTONE. Tombstoned
    slots are masked to -inf before the local comparator, so their ids can
    never be returned, and the slot is reused by a later `add_docs`. Global
    doc ids are never reused: `ids[s, slot]` maps slots to stable ids.

Device parallelism: `parallelism="shard_map"` scores the stacked macro
images over a REAL `jax.sharding.Mesh` — pass one explicitly via
`build(..., mesh=launch.mesh.make_macro_mesh())` or let it default to a
1-D mesh over every device — with per-device local scoring and a tiny
all-gather, exact monolithic parity included. A mesh whose size does not
divide `n_shards` is refused at build time: shard_map never quietly
becomes a one-device vmap. This module is also the
one blessed home of the pod-scale FLAT-index searcher
(`make_distributed_searcher` / `shard_index_arrays`, folded from the
retired `core.distributed`, which lives on as a deprecation shim).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import (
    bitplane,
    device_physics,
    error_detection,
    error_model,
    quantization,
    remapping,
    topk,
)
from .device_physics import DevicePhysics, DriftConfig
from .retrieval import RetrievalConfig, score_image

PARALLELISM = ("vmap", "map", "shard_map")

_NEG_INF = jnp.float32(-jnp.inf)


@partial(jax.jit, static_argnames=("cfg", "parallelism", "mesh"))
def _scores_impl(queries, values, scales, planes, norms, alive,
                 *, cfg: RetrievalConfig, parallelism: str,
                 mesh=None) -> jax.Array:
    """All-shard scores (S, b, cap), dead slots -inf. One XLA program per
    (config, parallelism, shape) combination — RetrievalConfig is frozen
    and hashable, so it rides along as a static argument (and so is
    `jax.sharding.Mesh`, so the explicit device mesh does too)."""
    q = quantization.quantize_query(queries, bits=cfg.bits)

    def shard_fn(values_s, scales_s, planes_s, norms_s):
        return score_image(cfg, q, queries, values_s, scales_s,
                           planes_s, norms_s)

    args = (values, scales, planes, norms)
    if parallelism == "map":
        s = jax.lax.map(lambda t: shard_fn(*t), args)
    elif parallelism == "shard_map":
        s = _shard_map_scores(shard_fn, args, mesh)
    else:
        s = jax.vmap(shard_fn)(*args)
    return jnp.where(alive[:, None, :], s, _NEG_INF)


def _shard_map_scores(shard_fn, args, mesh) -> jax.Array:
    """Distribute macros over a real device mesh along its leading axis.

    Each device scores its local block of shards (vmap inside the body)
    and the (S, b, cap) result is all-gathered back — candidate-list
    merging stays tiny exactly as in `make_distributed_searcher` below.
    `ShardedDircIndex.build` has checked that the mesh size divides
    n_shards.
    """
    from jax.sharding import PartitionSpec as P

    axes = mesh.axis_names

    def body(values, scales, planes_s, norms):
        local = jax.vmap(shard_fn)(values, scales, planes_s, norms)
        return jax.lax.all_gather(local, axes, axis=0, tiled=True)

    # check_vma=False: the output IS replicated (all_gather over every
    # mesh axis), but the checker cannot prove it through the kernels
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(),
        check_vma=False,
    )
    return mapped(*args)


@partial(jax.jit, static_argnames=("k", "kk", "n_cores"))
def _merge_impl(s, ids, alive, *, k: int, kk: int, n_cores: int) -> topk.TopK:
    """Per-macro top-k (16-core comparator tree when the capacity folds)
    then the cross-macro global comparator."""
    capacity = s.shape[-1]
    if capacity % n_cores == 0:
        per_shard = jax.vmap(
            lambda x: topk.hierarchical_topk(x, kk, n_cores=n_cores))(s)
    else:
        per_shard = jax.vmap(lambda x: topk.local_topk(x, kk))(s)
    lv, li = per_shard.scores, per_shard.indices          # (S, b, kk)
    # slot -> stable global id; dead slots surface as -1
    masked_ids = jnp.where(alive, ids, -1)                # (S, cap)
    gid = jax.vmap(lambda idv, lidx: idv[lidx])(masked_ids, li)
    b = lv.shape[1]
    cand_v = jnp.transpose(lv, (1, 0, 2)).reshape(b, -1)  # (b, S*kk)
    cand_i = jnp.transpose(gid, (1, 0, 2)).reshape(b, -1)
    # Global comparator: (-score, id) order matches jax.lax.top_k's
    # lower-index tie-break over a monolithic score row.
    merged = topk.merge_candidates(cand_v, cand_i, k)
    return topk.TopK(scores=merged.scores,
                     indices=merged.indices.astype(jnp.int32))


@dataclasses.dataclass
class ShardedDircIndex:
    """Corpus partitioned over `n_shards` simulated DIRC macros.

    All per-shard arrays are stacked on a leading shard axis and padded to a
    common `capacity`; `alive` masks padding and tombstones, `ids` maps
    (shard, slot) to stable global document ids (-1 = never written).
    """

    config: RetrievalConfig
    n_shards: int
    capacity: int
    values: jax.Array           # (S, cap, dim) int8 codes
    scales: jax.Array           # (S, cap, 1) fp32 per-document scales
    planes: jax.Array           # (S, cap, bits, dim) uint8 {0,1}
    lut: jax.Array              # (S, cap, bits) int32 D-Sum LUT
    norms: jax.Array            # (S, cap) fp32 integer norms
    ids: jax.Array              # (S, cap) int32 global doc ids, -1 = empty
    alive: jax.Array            # (S, cap) bool
    mapping: np.ndarray         # (S, slots, bits, 3) PER-MACRO bit->cell maps
    flip_probs: jax.Array       # (S, slots, bits) fp32 TRUE channel probs
    dim: int
    next_id: int
    parallelism: str = "vmap"
    mesh: Optional[object] = None  # jax.sharding.Mesh (shard_map only)
    physics: Optional[DevicePhysics] = None  # ground-truth error channels
    believed_maps: Optional[np.ndarray] = None  # (S, 8, 8) maps each
    #   shard's remapping was extracted against — diverges from
    #   physics.true_map(s) under drift until recalibrate_shard closes it

    def __post_init__(self) -> None:
        # Per-shard error/recal counters (host-side; tiny).
        #   cumulative: sense events, first-round Sigma-D detections,
        #     all-round detections, post-retry residual planes, recal
        #     events. window (reset by recalibrate_shard): per-(slot,
        #     bit) first-round detection counts — the raw material
        #     `extract_error_map` inverts back into a spatial map.
        s = self.n_shards
        slots, bits = self.flip_probs.shape[1], self.flip_probs.shape[2]
        self._senses = np.zeros(s, np.int64)
        self._first_det = np.zeros(s, np.int64)
        self._detected = np.zeros(s, np.int64)
        self._residual = np.zeros(s, np.int64)
        self._recals = np.zeros(s, np.int64)
        self._win_senses = np.zeros(s, np.int64)
        self._win_det_map = np.zeros((s, slots, bits), np.int64)

    # ---------------------------------------------------------------- build
    @classmethod
    def build(
        cls,
        embeddings: jax.Array,
        config: RetrievalConfig,
        n_shards: int = 4,
        parallelism: str = "vmap",
        mesh=None,
        drift: Optional[DriftConfig] = None,
        clock=None,
    ) -> "ShardedDircIndex":
        """`mesh` pins `parallelism="shard_map"` scoring to an explicit
        `jax.sharding.Mesh` (e.g. `launch.mesh.make_macro_mesh()`) —
        shards are split over its leading axis, one device group per
        macro block. None scores over a 1-D mesh of all devices. Raises
        ValueError when the mesh size does not divide `n_shards`.

        `drift` / `clock` configure the per-macro `DevicePhysics` channel
        (only meaningful with `config.error.enabled`): each shard gets
        its own jittered calibration and drift process over the
        injectable clock, and — for `mapping="error_aware"` — its own
        remapping extracted against its own t=0 calibration."""
        if parallelism not in PARALLELISM:
            raise ValueError(f"parallelism must be one of {PARALLELISM}")
        if mesh is not None and parallelism != "shard_map":
            raise ValueError(
                "mesh= only applies to parallelism='shard_map'")
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if parallelism == "shard_map":
            if mesh is None:
                from ._compat import make_mesh

                mesh = make_mesh((len(jax.devices()),), ("macro",))
            if n_shards % mesh.devices.size:
                raise ValueError(
                    f"parallelism='shard_map' needs n_shards ({n_shards}) "
                    f"to be a multiple of the mesh's {mesh.devices.size} "
                    "devices")
        emb = np.asarray(embeddings, np.float32)
        n, dim = emb.shape
        chunks = np.array_split(np.arange(n), n_shards)  # contiguous shards
        cap = max(1, max(len(c) for c in chunks))
        stacked = np.zeros((n_shards, cap, dim), np.float32)
        ids = np.full((n_shards, cap), -1, np.int32)
        alive = np.zeros((n_shards, cap), bool)
        for s, c in enumerate(chunks):
            stacked[s, : len(c)] = emb[c]
            ids[s, : len(c)] = c
            alive[s, : len(c)] = True

        docs = quantization.quantize(jnp.asarray(stacked), bits=config.bits,
                                     per_row=True)
        planes = bitplane.to_bitplanes(docs.values, bits=config.bits)
        physics = None
        believed = None
        if config.error.enabled:
            # Real dies: one error channel PER macro. Each shard's
            # remapping is extracted against its own t=0 calibration
            # (a perfect extraction — drift then degrades it).
            physics = DevicePhysics(config.error, n_shards,
                                    drift=drift, clock=clock)
            believed = physics.true_maps()
            mapping = np.stack([
                remapping.build_mapping_for_map(
                    config.mapping, config.bits,
                    believed[s] if config.mapping == "error_aware" else None)
                for s in range(n_shards)
            ])
            probs = jnp.asarray(physics.flip_probs(mapping), jnp.float32)
        else:
            base = remapping.build_mapping(
                config.mapping, bits=config.bits, error_cfg=config.error
            )
            mapping = device_physics.stack_mappings(base, n_shards)
            probs = jnp.asarray(
                np.broadcast_to(
                    error_model.flip_probs_for_mapping(base, config.error),
                    mapping.shape[:3]),
                dtype=jnp.float32,
            )
        return cls(
            config=config,
            n_shards=n_shards,
            capacity=cap,
            values=docs.values,
            scales=docs.scale,
            planes=planes,
            lut=bitplane.sum_d_lut(planes),
            norms=quantization.doc_int_norms(docs),
            ids=jnp.asarray(ids),
            alive=jnp.asarray(alive),
            mapping=mapping,
            flip_probs=probs,
            dim=dim,
            next_id=n,
            parallelism=parallelism,
            mesh=mesh,
            physics=physics,
            believed_maps=believed,
        )

    # ------------------------------------------------------------- counters
    @property
    def n_docs(self) -> int:
        """Live (non-tombstoned) documents across all shards."""
        return int(jnp.sum(self.alive))

    def shard_loads(self) -> np.ndarray:
        """(S,) live docs per shard — the add_docs balancing signal."""
        return np.asarray(jnp.sum(self.alive, axis=1))

    def _rows_per_slot(self) -> np.ndarray:
        """(slots,) how many rows of a shard land on each physical slot
        (row -> slot is `row % n_slots`, see `apply_sense_errors`)."""
        n_slots = self.mapping.shape[1]
        return np.bincount(np.arange(self.capacity) % n_slots,
                           minlength=n_slots)

    def stats(self) -> dict:
        """Per-shard error/recalibration counters + fleet rollup.

        `detected_rate` is first-round detections over first-round plane
        trials — an unbiased estimate of the channel's plane-mismatch
        probability (later rounds are conditioned on earlier mismatches).
        `exposure` is the ground-truth weighted error mass under the
        CURRENT mapping (what recalibration drives back down);
        `drift_amplitude`/`drift_phase` are simulation ground truth for
        reports, invisible to the controller.
        """
        plane_trials = self.capacity * self.config.bits
        shards = []
        for s in range(self.n_shards):
            senses = int(self._senses[s])
            trials = max(senses * plane_trials, 1)
            row = {
                "senses": senses,
                "detected": int(self._detected[s]),
                "residual": int(self._residual[s]),
                "detected_rate": float(self._first_det[s] / trials),
                "residual_rate": float(self._residual[s] / trials),
                "recal_events": int(self._recals[s]),
            }
            if self.physics is not None:
                row["drift_amplitude"] = float(
                    self.physics.drift_amplitude()[s])
                row["drift_phase"] = float(self.physics.drift_phase()[s])
                row["exposure"] = device_physics.weighted_exposure(
                    self.mapping[s], self.physics.true_map(s))
            shards.append(row)
        return {
            "n_shards": self.n_shards,
            "capacity": self.capacity,
            "live_docs": self.n_docs,
            "error_enabled": bool(self.config.error.enabled),
            "drift_enabled": bool(
                self.physics is not None and self.physics.drift.enabled),
            "total_senses": int(self._senses.sum()),
            "total_detected": int(self._detected.sum()),
            "total_residual": int(self._residual.sum()),
            "total_recals": int(self._recals.sum()),
            "shards": shards,
        }

    # ---------------------------------------------------------------- sense
    def _refresh_channel(self) -> None:
        """Advance the drift processes to the clock and resample the TRUE
        per-(slot, bit) probabilities under the current mappings. The
        believed maps / mappings are left alone — that gap is the point.
        """
        if self.physics is None or not self.physics.drift.enabled:
            return
        self.physics.advance()
        self.flip_probs = jnp.asarray(
            self.physics.flip_probs(self.mapping), jnp.float32)

    def _record_sense(self, res: error_detection.SenseResult) -> None:
        """Fold one sense event's per-shard counters into the stats.

        Host syncs a few KB per query batch — only on the error-enabled
        path, where the sense/detect loop already dominates.
        """
        dmap = np.asarray(res.detected_map, np.int64)     # (S, slots, bits)
        self._senses += 1
        self._first_det += dmap.sum(axis=(1, 2))
        self._detected += np.asarray(res.detected, np.int64)
        self._residual += np.asarray(res.residual_planes, np.int64)
        self._win_senses += 1
        self._win_det_map += dmap

    def _sensed_planes(self, key: Optional[jax.Array]) -> jax.Array:
        """Per-query transient sensing, one independent channel per macro.

        Each shard's transient key is `fold_in(key, shard)` — a stable
        per-macro identity, so shard s draws the same flips for the same
        query key regardless of fleet layout, and no two shards ever
        share a stream. Probs are per-shard (each macro its own map).
        """
        cfg = self.config
        if not cfg.error.enabled or key is None:
            return self.planes
        self._refresh_channel()
        keys = jnp.stack(
            [jax.random.fold_in(key, s) for s in range(self.n_shards)])
        retries = cfg.max_retries if cfg.detect else 0

        def sense(planes, lut, probs, k):
            return error_detection.sense_with_detection(
                planes, lut, probs, k,
                max_retries=retries, detect=cfg.detect,
            )

        args = (self.planes, self.lut, self.flip_probs, keys)
        if self.parallelism == "map":
            res = jax.lax.map(lambda t: sense(*t), args)
        else:
            res = jax.vmap(sense)(*args)
        self._record_sense(res)
        return res.planes

    # ---------------------------------------------------------------- score
    def scores(
        self, queries: jax.Array, key: Optional[jax.Array] = None
    ) -> jax.Array:
        """(b, dim) fp32 queries -> (S, b, cap) per-macro scores.

        Dead slots (padding/tombstones) are -inf.
        """
        if queries.ndim == 1:
            queries = queries[None]
        cfg = self.config
        # Same sensing gate as DircRagIndex.scores: the reference path
        # never reads planes, so don't pay the per-shard sense/detect loop.
        uses_planes = cfg.path in (
            "bitserial", "kernel_bitserial", "kernel_mxu"
        ) or (cfg.path == "int_exact" and cfg.error.enabled)
        planes = self._sensed_planes(key) if uses_planes else self.planes
        return _scores_impl(queries, self.values, self.scales, planes,
                            self.norms, self.alive, cfg=self.config,
                            parallelism=self.parallelism, mesh=self.mesh)

    # --------------------------------------------------------------- search
    def search(
        self, queries: jax.Array, k: int, key: Optional[jax.Array] = None
    ) -> topk.TopK:
        """Three-level comparator tree: cores -> macro -> global merge.

        Returns global doc ids; id -1 marks "fewer than k live documents".
        """
        if k > self.n_shards * self.capacity:
            raise ValueError(
                f"k={k} exceeds total slots {self.n_shards * self.capacity}")
        s = self.scores(queries, key=key)                    # (S, b, cap)
        return _merge_impl(s, self.ids, self.alive, k=k,
                           kk=min(k, self.capacity),
                           n_cores=self.config.n_cores)

    # --------------------------------------------------------------- update
    def _grow(self, extra: int) -> None:
        """Double capacity (at least `extra` new slots/shard) by padding."""
        new_cap = max(self.capacity * 2, self.capacity + extra)
        pad = new_cap - self.capacity

        def pad1(x, value=0):
            widths = [(0, 0)] * x.ndim
            widths[1] = (0, pad)
            return jnp.pad(x, widths, constant_values=value)

        self.values = pad1(self.values)
        self.scales = pad1(self.scales.astype(jnp.float32))
        self.scales = self.scales.at[:, self.capacity:].set(1.0)
        self.planes = pad1(self.planes)
        self.lut = pad1(self.lut)
        self.norms = pad1(self.norms)
        self.ids = pad1(self.ids, value=-1)
        self.alive = pad1(self.alive, value=False)
        self.capacity = new_cap

    def add_docs(self, embeddings: jax.Array) -> np.ndarray:
        """Write new documents into the least-loaded macros.

        Each row is quantized per-macro-row (scale, planes, LUT entry, norm
        recomputed for its slot), appended to the shard with the fewest live
        documents, reusing tombstoned slots first. Returns the new stable
        global ids, (m,) int32.
        """
        emb = jnp.atleast_2d(jnp.asarray(embeddings, jnp.float32))
        m = emb.shape[0]
        if emb.shape[1] != self.dim:
            raise ValueError(f"dim mismatch: got {emb.shape[1]}, want {self.dim}")

        loads = self.shard_loads().astype(np.int64)
        free = self.capacity - loads
        # Greedy balance on the host: always the least-loaded shard with a
        # free slot; grow every shard when the whole macro set is full.
        targets = np.empty((m,), np.int64)
        for j in range(m):
            open_shards = np.flatnonzero(free > 0)
            if open_shards.size == 0:
                self._grow(1)
                free = self.capacity - loads
                open_shards = np.flatnonzero(free > 0)
            s = open_shards[np.argmin(loads[open_shards])]
            targets[j] = s
            loads[s] += 1
            free[s] -= 1

        # One free slot per assignment, in target order (reuse tombstones).
        alive = np.array(self.alive)  # mutable host copy
        slots = np.empty((m,), np.int64)
        cursor: dict[int, int] = {}
        for j, s in enumerate(targets):
            start = cursor.get(s, 0)
            dead = np.flatnonzero(~alive[s, start:])
            slot = start + int(dead[0])
            slots[j] = slot
            alive[s, slot] = True
            cursor[s] = slot + 1

        docs = quantization.quantize(emb, bits=self.config.bits, per_row=True)
        new_planes = bitplane.to_bitplanes(docs.values, bits=self.config.bits)
        t = jnp.asarray(targets)
        sl = jnp.asarray(slots)
        new_ids = np.arange(self.next_id, self.next_id + m, dtype=np.int32)
        self.values = self.values.at[t, sl].set(docs.values)
        self.scales = self.scales.at[t, sl].set(docs.scale)
        self.planes = self.planes.at[t, sl].set(new_planes)
        self.lut = self.lut.at[t, sl].set(bitplane.sum_d_lut(new_planes))
        self.norms = self.norms.at[t, sl].set(quantization.doc_int_norms(docs))
        self.ids = self.ids.at[t, sl].set(jnp.asarray(new_ids))
        self.alive = self.alive.at[t, sl].set(True)
        self.next_id += m
        return new_ids

    def delete_docs(self, doc_ids: Sequence[int]) -> int:
        """Tombstone documents by stable global id. Returns #deleted.

        The ReRAM image is untouched (a real macro would not erase cells);
        only the alive bit flips, so the slot is masked out of every later
        search and becomes reusable by `add_docs`.
        """
        targets = jnp.asarray(np.asarray(list(doc_ids), np.int32))
        hit = jnp.isin(self.ids, targets) & self.alive
        n = int(jnp.sum(hit))
        self.alive = self.alive & ~hit
        return n

    # -------------------------------------------------------- recalibration
    def extract_error_map(self, shard: int) -> np.ndarray:
        """(8, 8) believed LSB map of one macro from its detection window.

        Inverts the since-last-recal first-round Sigma-D mismatch counts
        (per physical slot/bit) back into per-cell flip probabilities and
        scatters them through the shard's CURRENT mapping — the online
        analogue of the paper's offline Monte-Carlo map extraction.
        """
        trials = self._rows_per_slot() * max(int(self._win_senses[shard]), 1)
        return device_physics.extract_map_from_counts(
            self.mapping[shard], self._win_det_map[shard], trials, self.dim)

    def recalibrate_shard(
        self,
        shard: int,
        believed_map: Optional[np.ndarray] = None,
        chunk_rows: Optional[int] = None,
        on_chunk=None,
    ) -> np.ndarray:
        """Re-extract one macro's map, re-run remapping, re-encode in place.

        The index stays ONLINE throughout: the re-encode walks the
        shard's rows in chunks of `chunk_rows` (default capacity/4),
        rewriting planes + D-Sum LUT from the stored int8 codes —
        logical bit-plane content is mapping-invariant, so searches
        interleaved between chunks (exercise via `on_chunk(lo, hi)`)
        keep returning correct top-k. The mapping / channel-probability
        swap at the end is a single host-side assignment (atomic w.r.t.
        queries, which read a consistent snapshot per call).

        Returns the believed map the new remapping was extracted
        against. Resets the shard's detection window, so the controller
        baselines afresh against the post-recal channel.
        """
        cfg = self.config
        emap = (np.asarray(believed_map, np.float64)
                if believed_map is not None
                else self.extract_error_map(shard))
        new_mapping = remapping.build_mapping_for_map(
            cfg.mapping, cfg.bits,
            emap if cfg.mapping == "error_aware" else None)

        step = chunk_rows or max(1, self.capacity // 4)
        for lo in range(0, self.capacity, step):
            hi = min(lo + step, self.capacity)
            chunk = bitplane.to_bitplanes(
                self.values[shard, lo:hi], bits=cfg.bits)
            self.planes = self.planes.at[shard, lo:hi].set(chunk)
            self.lut = self.lut.at[shard, lo:hi].set(
                bitplane.sum_d_lut(chunk))
            if on_chunk is not None:
                on_chunk(lo, hi)

        new_mappings = np.array(self.mapping)
        new_mappings[shard] = new_mapping
        self.mapping = new_mappings
        if self.believed_maps is not None:
            self.believed_maps = np.array(self.believed_maps)
            self.believed_maps[shard] = emap
        if self.physics is not None:
            self.flip_probs = jnp.asarray(
                self.physics.flip_probs(self.mapping), jnp.float32)
        self._win_det_map[shard] = 0
        self._win_senses[shard] = 0
        self._recals[shard] += 1
        return emap

    # --------------------------------------------------------------- memory
    def storage_bytes(self) -> dict:
        """Per-macro ReRAM image + buffer, summed over allocated slots."""
        slots = self.n_shards * self.capacity
        emb = slots * self.dim * self.config.bits // 8
        buffer = slots * (4 + 4 + self.config.bits * 4 // 8)
        return {"embeddings": emb, "reram_buffer": buffer,
                "live_docs": self.n_docs}


# --------------------------------------------------------------------------
# Pod-scale flat-index searcher (folded from core.distributed).
#
# `ShardedDircIndex` stacks per-macro IMAGES and scores them over the
# macro mesh above; this is the complementary flat layout — one big
# (n, dim) int8 code matrix sharded along its doc axis, scored with the
# paper's comparator dataflow expressed directly in collectives:
#
#     doc shard per device (query-stationary: docs never move)
#       -> per-device INT8 scores               (local, zero collectives)
#       -> per-device local top-k               (the "local comparator")
#       -> all_gather of (k, score, id) triples (the "SRAM buffer": tiny)
#       -> global top-k                         (the "global comparator")
#
# The all-gather payload is k * 8 bytes * devices — e.g. 512 devices,
# k=16: 64 KB total, mirroring the paper's "<1 KB SRAM buffer" argument.
# `shard_map` is required (not bare GSPMD) because *local* top-k
# semantics — top-k per shard, not global top-k — cannot be expressed as
# a sharding constraint on a global op. `core.distributed` re-exports
# these under a DeprecationWarning.
# --------------------------------------------------------------------------

def _flat_axis_index(axis_names: Sequence[str]) -> jax.Array:
    """Linear device index over (possibly multiple) mesh axes."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def _local_search(q, docs, norms, *, k: int, metric: str, axis_names):
    """Per-shard body: score + local top-k + gather + global merge."""
    # q: (b, dim) int8 replicated; docs: (n_local, dim) int8; norms: (n_local,)
    ip = jax.lax.dot_general(
        q.astype(jnp.int32),
        docs.astype(jnp.int32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    if metric == "cosine":
        qn = jnp.sqrt(jnp.sum(q.astype(jnp.float32) ** 2, -1, keepdims=True))
        scores = ip / jnp.maximum(qn * norms[None, :], 1e-12)
    else:
        scores = ip
    n_local = docs.shape[0]
    kk = min(k, n_local)
    lv, li = jax.lax.top_k(scores, kk)                     # (b, k) local
    shard = _flat_axis_index(axis_names)
    gid = li.astype(jnp.int32) + shard * n_local           # global doc ids
    # All-gather the candidate lists (tiny) and merge.
    av = jax.lax.all_gather(lv, axis_names, axis=1, tiled=True)  # (b, P*k)
    ai = jax.lax.all_gather(gid, axis_names, axis=1, tiled=True)
    gv, gpos = jax.lax.top_k(av, k)
    gi = jnp.take_along_axis(ai, gpos, axis=1)
    return gv, gi


def make_distributed_searcher(
    mesh,
    k: int,
    metric: str = "cosine",
    doc_axes: Sequence[str] | None = None,
):
    """Build a jit'd flat-index searcher over `mesh`.

    Docs are sharded along their first axis over `doc_axes` (default: all
    mesh axes — every device holds a distinct database shard, the maximal
    'core count'). Queries are replicated (query-stationary broadcast).

    Returns fn(q_int8 (b, dim), docs_int8 (n, dim), norms (n,)) -> TopK,
    with outputs replicated.
    """
    from jax.sharding import PartitionSpec as P

    doc_axes = tuple(doc_axes if doc_axes is not None else mesh.axis_names)
    doc_spec = P(doc_axes)
    body = partial(_local_search, k=k, metric=metric, axis_names=doc_axes)
    shmapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), doc_spec, doc_spec),
        out_specs=(P(), P()),
        check_vma=False,  # outputs ARE replicated (all_gather over all
                          # doc axes + identical top_k); the checker
                          # cannot prove it through top_k
    )

    @jax.jit
    def search(q, docs, norms) -> topk.TopK:
        v, i = shmapped(q, docs, norms)
        return topk.TopK(scores=v, indices=i)

    return search


def shard_index_arrays(mesh, docs_values, doc_norms, doc_axes=None):
    """Place flat-index arrays with the sharding the searcher expects."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    doc_axes = tuple(doc_axes if doc_axes is not None else mesh.axis_names)
    ds = NamedSharding(mesh, P(doc_axes))
    ns = NamedSharding(mesh, P(doc_axes))
    return jax.device_put(docs_values, ds), jax.device_put(doc_norms, ns)
