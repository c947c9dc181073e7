"""Transient-rollout engine: prefill / insert / generate serving.

The MeshGraphNet lineage is autoregressive — one request wants a T-step
pressure/velocity rollout, not a single static prediction. This module
refactors that lifecycle the way LLM decode engines (maxtext's
prefill/insert/generate split) do, applied to physics stepping:

- **prefill**: build the multi-scale graph and featurize ONCE per geometry
  (one jitted program reusing the graphx pipeline + the server's bucket
  ladder and calibration caches). The graph is step-invariant; a T-step
  rollout pays for it exactly once.
- **insert**: park the prefilled graph, the normalizer state (folded into
  the compiled programs) and the current field state in a device-resident
  **slot table** keyed by rollout id — per-bucket ``(S, ...)`` arrays whose
  leading axis is the slot.
- **generate**: one jitted ``lax.scan`` advances EVERY active rollout in a
  table by ``steps_per_flush`` physics steps per call, slots as ``vmap``
  lanes. Rollouts of different lengths and mid-flight arrivals interleave:
  a per-lane ``remaining`` counter freezes finished/idle lanes inside the
  program, and lane independence is structural (a diverging rollout cannot
  leak into its neighbors).

Single-shot serving is the T=1 special case of this engine — the serving
forward pass IS featurize + one step from a zero state
(``graphx.pipeline.make_graph_forward``), which ``tests/test_rollout.py``
pins bit-equal.

Sharding: under ``shard_devices > 1`` the table's slot axis rides the
shard_map program's pack axis (PR 9's packing substrate) via
``graphx.sharded.make_sharded_rollout_fn``. With the default
``rollout_state_feats=False`` the field state never re-enters message
passing, so multi-step scans inside one flush stay exact on owned rows;
with state feedback the halo rings cover exactly one step, so the engine
clamps to one step per flush and performs a host-side halo exchange
(``ShardPlan.gather`` → ``ShardPlan.scatter``) between flushes.

Resilience (riding ``repro.resilience``): fault sites
``rollout.prefill`` / ``rollout.insert`` / ``rollout.generate`` /
``rollout.harvest`` chaos-test the slot table; the nonfinite guard checks
every active lane each flush and aborts ONLY the diverging rollout;
per-rollout deadlines bound generate-queue blowup (an expired rollout is
aborted, queued or mid-flight, without touching its neighbors).

Telemetry: per-flush ``rollout_generate`` spans plus per-rollout
``rollout_prefill`` / ``rollout_insert`` / ``rollout`` spans stitched by
``trace_id=roll-<rid>``; Prometheus counters ``rollout_steps_total``,
``rollouts_completed_total``, ``rollouts_aborted_total``,
``rollouts_timed_out_total``, ``rollouts_rejected_total`` and the
``rollout_active_slots`` gauge ride the server's metrics registry.
"""
from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphx import sharded
from repro.graphx.pipeline import make_generate_fn, make_prefill_fn
from repro.resilience import faults

ROLLOUT_STAGES = ("rollout_prefill", "rollout_insert", "rollout_generate",
                  "rollout_harvest")


@dataclass
class RolloutRequest:
    """One queued/active rollout (host bookkeeping; state lives on device)."""
    verts: np.ndarray
    faces: np.ndarray
    rollout_id: int
    steps: int
    bucket: int
    n_points: Optional[int] = None
    t_submit: float = 0.0
    deadline: Optional[float] = None
    init_state: Optional[np.ndarray] = None   # (bucket, node_out) start state
    cloud: Optional[tuple] = None             # (points, normals) override


@dataclass
class RolloutResult:
    rollout_id: int
    points: np.ndarray                 # (n, 3) sampled surface points
    fields: np.ndarray                 # (n, node_out) final field state
    steps: int                         # steps requested
    steps_done: int                    # steps actually advanced
    latency_s: float
    bucket: int
    error: Optional[str] = None


class _SlotTable:
    """Device-resident rollout state for ONE bucket size.

    Unsharded layout: every prefilled-graph leaf carries a leading slot
    axis ``(S, ...)``, ``state`` is ``(S, n, node_out)`` and the jitted
    generate program vmaps over the slot axis. ``remaining`` is mirrored on
    host (it is derived data — each flush subtracts ``steps_per_flush``
    deterministically), so freeing or aborting a slot never needs a device
    round-trip.

    Sharded layout: leaves are ``(P, G, Nmax, ...)`` with the slot axis on
    the shard_map program's pack axis G; per-lane ``ShardPlan``s handle the
    host-side gather/scatter.
    """

    def __init__(self, size: int, slots: int):
        self.size = size
        self.slots = slots
        self.graph: Optional[dict] = None       # device pytree, slot-leading
        self.state = None                       # device (S, n, out) | (P,G,N,out)
        self.rem = np.zeros((slots,), np.int64)  # host mirror of steps owed
        self.reqs: List[Optional[RolloutRequest]] = [None] * slots
        self.pts: List[Optional[np.ndarray]] = [None] * slots
        self.plans: List[Optional[sharded.ShardPlan]] = [None] * slots
        self.gstate: List[Optional[np.ndarray]] = [None] * slots

    def free_slot(self) -> Optional[int]:
        for s, r in enumerate(self.reqs):
            if r is None:
                return s
        return None

    def active(self) -> List[int]:
        return [s for s, r in enumerate(self.reqs) if r is not None]

    def release(self, slot: int):
        self.reqs[slot] = None
        self.pts[slot] = None
        self.plans[slot] = None
        self.gstate[slot] = None
        self.rem[slot] = 0


class RolloutEngine:
    """Prefill/insert/generate rollout serving on top of a ``GNNServer``.

    The engine composes with (rather than forks) the server: it reuses the
    bucket ladder and routing (``_route``), the per-size calibration caches
    (``_calibrate`` / ``_calibrate_shard``), the request-id space and
    deterministic ``(seed, rid)`` surface sampling, the telemetry registry
    and the resilience knobs. It is driven synchronously: every
    :meth:`generate` call is one flush (admit → advance → harvest);
    :meth:`result` drives flushes until the rollout resolves.
    """

    def __init__(self, server, *, slots: Optional[int] = None,
                 steps_per_flush: Optional[int] = None):
        cfg = server.cfg
        if server.is_volume:
            raise ValueError("the unet family has no rollout mode: X-UNet3D "
                             "answers one steady volume per geometry")
        self.server = server
        self.slots = max(int(cfg.rollout_slots if slots is None else slots), 1)
        spf = int(cfg.rollout_steps_per_flush if steps_per_flush is None
                  else steps_per_flush)
        self.sharded_mode = server.shard_devices > 1
        if self.sharded_mode and cfg.rollout_state_feats and spf != 1:
            # the halo rings make each shard self-contained for exactly ONE
            # step once state re-enters message passing; more would read
            # stale halo state. Clamp + host halo exchange between flushes.
            warnings.warn(
                "sharded rollouts with rollout_state_feats=True are exact "
                "for one step per flush only (halo staleness): clamping "
                f"steps_per_flush {spf} -> 1")
            spf = 1
        self.steps_per_flush = max(spf, 1)
        self.timeout_s = float(getattr(cfg, "rollout_timeout_s", 0.0))
        self.max_pending = int(server.max_queue_depth)
        self._tables: Dict[int, _SlotTable] = {}
        self._prefill: Dict[int, object] = {}
        self._gen: Dict[int, object] = {}
        self._insert: Dict[int, object] = {}
        self._queue: deque = deque()
        self._results: Dict[int, RolloutResult] = {}
        self._lock = threading.RLock()
        m = server.telemetry.metrics
        self._c_steps = m.counter(
            "rollout_steps_total", help="physics steps advanced (all slots)")
        self._c_done = m.counter(
            "rollouts_completed_total", help="rollouts finished cleanly")
        self._c_abort = m.counter(
            "rollouts_aborted_total",
            help="rollouts aborted (nonfinite / fault / generate failure)")
        self._c_timeout = m.counter(
            "rollouts_timed_out_total", help="rollouts expired by deadline")
        self._c_reject = m.counter(
            "rollouts_rejected_total", help="rollouts shed at admission")
        self._g_active = m.gauge(
            "rollout_active_slots", help="slots currently mid-rollout")

    # ------------------------------------------------------------ programs

    def _programs(self, size: int):
        """(prefill, generate, insert) jitted programs for one bucket size,
        built once and cached — calibration rides the server's per-size
        spec caches, so an engine on a restored server re-pays nothing."""
        srv = self.server
        if size in self._gen:
            return (self._prefill.get(size), self._gen[size],
                    self._insert.get(size))
        cfg = srv.cfg
        ms = srv._calibrate(size)
        donate = srv._donate and jax.default_backend() != "cpu"
        if self.sharded_mode:
            sspec = srv._calibrate_shard(size, ms)
            gen = sharded.make_sharded_rollout_fn(
                cfg, sspec, srv._mesh, steps=self.steps_per_flush,
                knn_impl=srv._knn_impl,
                norm_in=srv._norm_in, norm_out=srv._norm_out,
                pack_width=self.slots)
            prefill = None
        else:
            prefill = make_prefill_fn(
                cfg, ms, knn_impl=srv._knn_impl, norm_in=srv._norm_in)
            gen = make_generate_fn(
                cfg, steps=self.steps_per_flush, norm_out=srv._norm_out,
                donate=srv._donate)

        def insert_tree(graph, state, new_graph, new_state, slot):
            if self.sharded_mode:
                upd = lambda t, u: t.at[:, slot].set(u)
            else:
                upd = lambda t, u: t.at[slot].set(u)
            return (jax.tree_util.tree_map(upd, graph, new_graph),
                    upd(state, new_state))

        insert = (jax.jit(insert_tree, static_argnums=(4,),
                          donate_argnums=(0, 1)) if donate
                  else jax.jit(insert_tree, static_argnums=(4,)))
        self._prefill[size], self._gen[size], self._insert[size] = \
            prefill, gen, insert
        return prefill, gen, insert

    def _table(self, size: int) -> _SlotTable:
        t = self._tables.get(size)
        if t is None:
            t = self._tables[size] = _SlotTable(size, self.slots)
        return t

    # ------------------------------------------------------------ submit

    def submit(self, verts: np.ndarray, faces: np.ndarray,
               n_points: Optional[int] = None, *, steps: int = 1,
               timeout_s: Optional[float] = None,
               init_state: Optional[np.ndarray] = None,
               cloud: Optional[tuple] = None) -> int:
        """Enqueue a T-step rollout; returns the rollout id.

        Ids are allocated from the server's request-id space, so a rollout
        samples the identical ``(seed, rid)`` surface cloud a single-shot
        request with the same id would — the T=1 equivalence is exact, not
        statistical. ``init_state`` ((bucket, node_out)) seeds the field
        state (default zeros — the single-shot convention); ``cloud``
        bypasses sampling with an explicit ``(points, normals)`` pair
        (sequential-stepping tests chain rollouts on one fixed cloud).
        ``timeout_s`` (default ``cfg.rollout_timeout_s``) bounds the
        rollout end-to-end — queued or mid-generate.
        """
        srv = self.server
        verts = np.asarray(verts, np.float32)
        faces = np.asarray(faces)
        bucket = srv._route(n_points, mutate=True)
        t0 = time.perf_counter()
        with srv._cond:
            rid = srv._next_id
            srv._next_id += 1
        if timeout_s is None:
            timeout_s = self.timeout_s or None
        req = RolloutRequest(
            verts=verts, faces=faces, rollout_id=rid, steps=max(int(steps), 1),
            bucket=bucket, n_points=n_points, t_submit=t0,
            deadline=None if not timeout_s else t0 + float(timeout_s),
            init_state=(None if init_state is None
                        else np.asarray(init_state, np.float32)),
            cloud=cloud)
        with self._lock:
            if self.max_pending > 0 and self.pending() >= self.max_pending:
                self._c_reject.inc()
                self._results[rid] = self._error_result(
                    req, f"rejected: rollout queue full "
                    f"(max_queue_depth={self.max_pending})", steps_done=0)
                return rid
            self._queue.append(req)
        if srv.telemetry.enabled:
            srv.telemetry.tracer.record_span(
                "rollout_submit", t0, time.perf_counter(),
                trace_id=f"roll-{rid}", bucket=bucket, steps=req.steps)
        return rid

    def pending(self) -> int:
        """Rollouts not yet resolved: queued + mid-flight."""
        return len(self._queue) + sum(len(t.active())
                                      for t in self._tables.values())

    # ------------------------------------------------------------ results

    def _error_result(self, req: RolloutRequest, reason: str,
                      steps_done: int) -> RolloutResult:
        t = time.perf_counter()
        return RolloutResult(
            rollout_id=req.rollout_id, points=np.zeros((0, 3), np.float32),
            fields=np.full((req.bucket, self.server.cfg.node_out), np.nan,
                           np.float32),
            steps=req.steps, steps_done=steps_done,
            latency_s=t - (req.t_submit or t), bucket=req.bucket,
            error=reason)

    def _finish(self, req: RolloutRequest, res: RolloutResult):
        self._results[req.rollout_id] = res
        srv = self.server
        if srv.telemetry.enabled:
            t = time.perf_counter()
            srv.telemetry.tracer.record_span(
                "rollout", req.t_submit or t, t,
                trace_id=f"roll-{req.rollout_id}", bucket=req.bucket,
                steps=res.steps_done, error=res.error)

    def result(self, rollout_id: int, *, drive: bool = True
               ) -> Optional[RolloutResult]:
        """Fetch (and pop) a rollout's result.

        The engine is synchronously driven: with ``drive=True`` (default)
        this runs :meth:`generate` flushes until the rollout resolves.
        ``drive=False`` only polls (returns None when unresolved).
        """
        while True:
            with self._lock:
                res = self._results.pop(rollout_id, None)
                if res is not None:
                    return res
                if not drive or self.pending() == 0:
                    return None
            self.generate()

    def run_until_complete(self) -> int:
        """Drive flushes until nothing is pending; returns flush count."""
        flushes = 0
        while self.pending() > 0:
            self.generate()
            flushes += 1
        return flushes

    # ------------------------------------------------------------ admit

    def _admit_locked(self):
        now = time.perf_counter()
        kept = deque()
        while self._queue:
            req = self._queue.popleft()
            if req.deadline is not None and now > req.deadline:
                self._c_timeout.inc()
                self._finish(req, self._error_result(
                    req, f"rollout timed out after {self.timeout_s:.3f}s "
                    "before any generate flush", steps_done=0))
                continue
            table = self._table(req.bucket)
            slot = table.free_slot()
            if slot is None:
                kept.append(req)     # this bucket is full; others may admit
                continue
            try:
                self._insert_rollout(table, slot, req)
            except Exception as e:      # noqa: BLE001 — chaos/prefill failure
                self._c_abort.inc()
                self._finish(req, self._error_result(
                    req, f"prefill/insert failed: {e or e.__class__.__name__}",
                    steps_done=0))
        self._queue = kept

    def _init_state(self, req: RolloutRequest) -> np.ndarray:
        n, out = req.bucket, self.server.cfg.node_out
        if req.init_state is None:
            return np.zeros((n, out), np.float32)
        st = np.asarray(req.init_state, np.float32)
        if st.shape != (n, out):
            raise ValueError(
                f"init_state shape {st.shape} != bucket state ({n}, {out})")
        return st

    def _sample_cloud(self, req: RolloutRequest) -> Tuple[np.ndarray,
                                                          np.ndarray]:
        if req.cloud is not None:
            pts, nrm = req.cloud
            return (np.asarray(pts, np.float32), np.asarray(nrm, np.float32))
        from repro.launch.serve_gnn import Request
        return self.server._sample(
            Request(req.verts, req.faces, req.rollout_id, req.n_points),
            req.bucket)

    def _insert_rollout(self, table: _SlotTable, slot: int,
                        req: RolloutRequest):
        """prefill (graph+featurize once) then park it in the slot table."""
        srv = self.server
        prefill, gen, insert = self._programs(table.size)
        t0 = time.perf_counter()
        faults.fire("rollout.prefill")
        pts, nrm = self._sample_cloud(req)
        st0 = self._init_state(req)
        st0 = faults.corrupt("rollout.insert", st0)
        if self.sharded_mode:
            self._insert_sharded(table, slot, req, pts, nrm, st0, insert, t0)
            return
        graph = prefill(jnp.asarray(pts), jnp.asarray(nrm),
                        np.int32(table.size))
        t1 = time.perf_counter()
        srv.stats.record_stage("rollout_prefill", t1 - t0)
        faults.fire("rollout.insert")
        if table.graph is None:
            # first insert materializes the table: zero lanes are inert
            # (emask False masks every edge; remaining 0 freezes the state)
            table.graph = jax.tree_util.tree_map(
                lambda v: jnp.zeros((self.slots,) + v.shape, v.dtype), graph)
            table.state = jnp.zeros(
                (self.slots, table.size, srv.cfg.node_out), jnp.float32)
        table.graph, table.state = insert(
            table.graph, table.state, graph, jnp.asarray(st0), slot)
        self._commit_slot(table, slot, req, pts, t1)

    def _insert_sharded(self, table: _SlotTable, slot: int,
                        req: RolloutRequest, pts, nrm, st0, insert,
                        t0: float):
        """Sharded prefill = host shard planning; the graph build itself
        happens in-program each flush (same policy as sharded serving)."""
        from repro.launch.sharding import shard_put
        srv = self.server
        sspec = srv._shard_calib[table.size]
        faults.fire("shard.plan")
        plan = sharded.plan_shards(
            pts, nrm, srv.shard_devices, srv.cfg.n_mp_layers,
            sspec.ms.level_sizes, srv.cfg.k_neighbors, method="geometric",
            halo_width=(sspec.halo_width
                        or sharded.global_halo_width(pts, sspec.ms)),
            spec=sspec)
        batch = shard_put(plan.batch(), srv._mesh)
        st_local = jnp.asarray(plan.scatter(st0))
        t1 = time.perf_counter()
        srv.stats.record_stage("rollout_prefill", t1 - t0)
        faults.fire("rollout.insert")
        if table.graph is None:
            table.graph = {k: jnp.repeat(v[:, None], self.slots, axis=1)
                           for k, v in batch.items()}
            table.state = jnp.zeros(
                (srv.shard_devices, self.slots) + st_local.shape[1:],
                jnp.float32)
        table.graph, table.state = insert(
            table.graph, table.state, batch, st_local, slot)
        table.plans[slot] = plan
        table.gstate[slot] = np.asarray(st0)
        self._commit_slot(table, slot, req, pts, t1)

    def _commit_slot(self, table: _SlotTable, slot: int, req: RolloutRequest,
                     pts: np.ndarray, t1: float):
        srv = self.server
        table.reqs[slot] = req
        table.pts[slot] = pts
        table.rem[slot] = req.steps
        t2 = time.perf_counter()
        srv.stats.record_stage("rollout_insert", t2 - t1)
        if srv.telemetry.enabled:
            srv.telemetry.tracer.record_span(
                "rollout_prefill", req.t_submit, t1,
                trace_id=f"roll-{req.rollout_id}", bucket=table.size)
            srv.telemetry.tracer.record_span(
                "rollout_insert", t1, t2, trace_id=f"roll-{req.rollout_id}",
                bucket=table.size, slot=slot)

    # ------------------------------------------------------------ generate

    def generate(self) -> int:
        """One flush: admit queued rollouts into free slots, advance every
        active table ``steps_per_flush`` steps, harvest finished / diverged
        / expired slots. Returns the number of rollouts still pending."""
        with self._lock:
            self._admit_locked()
            for size in sorted(self._tables):
                table = self._tables[size]
                if table.active():
                    self._advance_table(table)
                    self._harvest_table(table)
            self._g_active.set(sum(len(t.active())
                                   for t in self._tables.values()))
            return self.pending()

    def _advance_table(self, table: _SlotTable):
        srv = self.server
        _, gen, _ = self._programs(table.size)
        spf = self.steps_per_flush
        t0 = time.perf_counter()
        try:
            faults.fire("rollout.generate")
            if self.sharded_mode:
                state = self._advance_sharded(table, gen)
            else:
                rem_dev = jnp.asarray(table.rem.astype(np.int32))
                state, _ = gen(srv.params, table.graph, table.state, rem_dev)
            table.state = jax.block_until_ready(state)
        except Exception as e:           # noqa: BLE001 — chaos/XLA failure
            # a failed flush kills THIS table's in-flight rollouts (their
            # device state is unrecoverable) but not the queue or other
            # buckets' tables
            for slot in table.active():
                req = table.reqs[slot]
                self._c_abort.inc()
                self._finish(req, self._error_result(
                    req, f"generate flush failed: {e or e.__class__.__name__}",
                    steps_done=req.steps - int(table.rem[slot])))
                table.release(slot)
            # the device arrays may have been donated into the failed call:
            # drop them; the next insert rematerializes a fresh table
            table.graph = None
            table.state = None
            return
        advanced = int(np.minimum(table.rem, spf).sum())
        table.rem = np.maximum(table.rem - spf, 0)
        self._c_steps.inc(advanced)
        t1 = time.perf_counter()
        srv.stats.record_stage("rollout_generate", t1 - t0)
        if srv.telemetry.enabled:
            srv.telemetry.tracer.record_span(
                "rollout_generate", t0, t1, bucket=table.size,
                active=len(table.active()), steps=spf, advanced=advanced)

    def _advance_sharded(self, table: _SlotTable, gen):
        srv = self.server
        cfg = srv.cfg
        if cfg.rollout_state_feats:
            # host halo exchange: every lane's global state is re-scattered
            # so halo rows carry their owners' CURRENT values (one exact
            # step per flush — steps_per_flush is clamped to 1)
            rows = []
            for g in range(self.slots):
                plan, gs = table.plans[g], table.gstate[g]
                if plan is None:
                    rows.append(np.zeros(
                        (srv.shard_devices,) + tuple(table.state.shape[2:]),
                        np.float32))
                else:
                    rows.append(plan.scatter(gs))
            table.state = jnp.asarray(np.stack(rows, axis=1))
        rem = np.broadcast_to(table.rem.astype(np.int32)[None, :],
                              (srv.shard_devices, self.slots))
        state, _ = gen(srv.params, table.graph, table.state,
                       jnp.asarray(rem))
        if cfg.rollout_state_feats:
            out = np.asarray(state)
            for g in range(self.slots):
                if table.plans[g] is not None and table.rem[g] > 0:
                    table.gstate[g] = table.plans[g].gather(out[:, g])
        return state

    # ------------------------------------------------------------ harvest

    def _lane_finite(self, table: _SlotTable) -> np.ndarray:
        """(S,) finiteness verdict per lane from one cheap device reduce
        (abs-sum per lane; NaN/Inf propagate), not a full state transfer."""
        if self.sharded_mode:
            tot = jnp.sum(jnp.abs(table.state), axis=(0, 2, 3))
        else:
            tot = jnp.sum(jnp.abs(table.state), axis=(1, 2))
        return np.isfinite(np.asarray(tot))

    def _slot_fields(self, table: _SlotTable, slot: int) -> np.ndarray:
        if self.sharded_mode:
            if self.server.cfg.rollout_state_feats:
                return np.asarray(table.gstate[slot])
            return table.plans[slot].gather(
                np.asarray(table.state[:, slot]))
        return np.asarray(table.state[slot])

    def _harvest_table(self, table: _SlotTable):
        srv = self.server
        if table.state is None or not table.active():
            return                        # flush failed: slots already failed
        guard = srv.cfg.nonfinite_guard
        t0 = time.perf_counter()
        lane_ok = self._lane_finite(table) if guard else None
        now = time.perf_counter()
        for slot in table.active():
            req = table.reqs[slot]
            done = req.steps - int(table.rem[slot])
            if guard and not lane_ok[slot]:
                # the diverging rollout dies; its vmap-lane neighbors are
                # untouched (lane independence is structural)
                srv.stats.bump("nonfinite_results")
                self._c_abort.inc()
                self._finish(req, self._error_result(
                    req, f"nonfinite state detected at rollout step {done} "
                    f"(bucket {table.size}, slot {slot}); rollout aborted",
                    steps_done=done))
                table.release(slot)
                continue
            if table.rem[slot] == 0:
                fields = faults.corrupt("rollout.harvest",
                                        self._slot_fields(table, slot))
                if guard and not np.isfinite(fields).all():
                    srv.stats.bump("nonfinite_results")
                    self._c_abort.inc()
                    self._finish(req, self._error_result(
                        req, "nonfinite output at rollout harvest "
                        f"(bucket {table.size}, slot {slot})",
                        steps_done=done))
                    table.release(slot)
                    continue
                t = time.perf_counter()
                self._c_done.inc()
                self._finish(req, RolloutResult(
                    rollout_id=req.rollout_id, points=table.pts[slot],
                    fields=fields, steps=req.steps, steps_done=done,
                    latency_s=t - (req.t_submit or t), bucket=table.size))
                table.release(slot)
                continue
            if req.deadline is not None and now > req.deadline:
                self._c_timeout.inc()
                self._finish(req, self._error_result(
                    req, f"rollout deadline expired mid-flight after "
                    f"{done}/{req.steps} steps", steps_done=done))
                table.release(slot)
        srv.stats.record_stage("rollout_harvest", time.perf_counter() - t0)
