"""graftcast prefetch — forecast-driven tier promotion ahead of the
epoch tick.

grafttier (PR 14) promotes REACTIVELY: a shifting hot set pays the
cold tier's host-link bandwidth on the serving path until the next
placement epoch catches up. Every signal a predictor needs already
exists — the claimed probe-frequency window the epoch plans from and
graftledger's live headroom — so this module closes the gap with
three pieces, none of which adds a compile or a serving-path stall:

- **Forecast** (:func:`forecast_plan`) — a pure, deterministic
  function: the per-epoch claimed windows fold into a per-list EWMA
  (``alpha`` per epoch — the :class:`~raft_tpu.serving.gauge
  .DriftDetector` convention), and the NEXT epoch's plan is predicted
  by running the very :func:`~raft_tpu.serving.placement.plan_epoch`
  policy over the smoothed counts. Same inputs → same prediction on
  every replica; no clock, no RNG.
- **Staged promotion channel** (:meth:`TierPrefetcher.prefetch`) —
  at the :class:`~raft_tpu.serving.placement.TierManager`'s lead-time
  tick, predicted promotions copy their cold blocks into a fixed
  ``(K, ...)`` staged plane per hot plane — one donated
  ``dynamic_update_index_in_dim`` program per plane geometry
  (:func:`_stage_row_fn`), compiled once and reused forever, so the
  prefetcher adds ZERO compiles to a warm service. The copy out of
  the host-committed cold plane IS the promotion DMA, issued in the
  background instead of inside the epoch; at the epoch,
  :meth:`TierPrefetcher.take` hands :func:`~raft_tpu.neighbors.tiered
  .apply_plan` the staged rows and only the MISSES stream from the
  cold tier on the epoch path (the ``tier.promote_cold_bytes``
  surface ``BENCH_TIERED`` gates).
- **Miss cache + capacity discipline** — the staged planes double as
  a cold-tier miss cache pinning the last ``K``
  promoted-but-unplaced blocks in spare HBM. ``K`` is sized from
  live ledger headroom at construction, and the ACTIVE staged bytes
  ride the ledger as a named reservation
  (:meth:`~raft_tpu.core.memwatch.MemoryLedger.reserve`) through the
  capacity gate: a prefetch that would not fit raises
  :class:`~raft_tpu.core.memwatch.CapacityExceeded` HOST-side and the
  prefetcher degrades to the reactive path (counted, never an error
  on a search), and :meth:`TierPrefetcher.maintain` evicts
  least-recently-staged rows when headroom shrinks under it.

Staleness: every staged row is stamped with the tiered container's
placement ``generation``. :func:`~raft_tpu.neighbors.tiered
.apply_plan` bumps it under the swap lock, so a prefetch that
completes after the epoch it aimed at (or after its list was demoted
again) is detectably stale — :meth:`take` refuses the row and counts
it ``tier.prefetch.cancelled``; the promotion falls back to the cold
stream and stays bit-identical.

Counters: ``tier.prefetch.{issued,hits,misses,cancelled}`` (federated
into ``/fleet.json`` like the other tier counters).

Clock discipline (graftlint R7 — this module is IN scope): the
prefetcher holds NO clock at all. Lead-time pacing lives in
:meth:`TierManager.tick` on its injected clock; the prefetcher's only
notion of order is a logical stage counter (LRU age) and the
container's placement generation.

Host-sync discipline (R5 — in scope): the stage path enqueues device
programs and keeps every decision (row choice, generation stamp,
byte accounting) in host numpy; nothing fetches a device array.
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import memwatch, tracing
from raft_tpu.core.memwatch import CapacityExceeded
from raft_tpu.core.validation import expect

ISSUED = "tier.prefetch.issued"
HITS = "tier.prefetch.hits"
MISSES = "tier.prefetch.misses"
CANCELLED = "tier.prefetch.cancelled"


@dataclasses.dataclass(frozen=True)
class PrefetchConfig:
    """Forecast + staging knobs. ``alpha`` is the per-epoch EWMA fold
    (the DriftDetector convention: higher = faster adaptation, more
    noise). ``capacity`` fixes the staged-plane row count ``K``;
    ``None`` sizes it from the swap width, clamped by ledger headroom
    × (1 − ``safety_fraction``) when a ledger with known headroom is
    attached. ``min_heat_ratio`` is the forecast's hysteresis —
    default matches the placement policy so the prediction is the
    plan the epoch would run on the smoothed window.
    ``prior_weight`` scales the EWMA against the live rolling window
    in the forecast fold (see :func:`forecast_plan`)."""

    alpha: float = 0.3
    capacity: Optional[int] = None
    safety_fraction: float = 0.25
    min_heat_ratio: float = 1.5
    prior_weight: float = 0.25


@dataclasses.dataclass(frozen=True)
class StagedBlocks:
    """What :func:`~raft_tpu.neighbors.tiered.apply_plan` consumes:
    ``rows[i]`` is the staged-plane row holding ``promotions[i]``'s
    blocks (−1 = miss, stream from cold), ``planes`` maps each hot
    plane name to its fixed ``(K, ...)`` staged storage."""

    rows: np.ndarray
    planes: Dict[str, jax.Array]


def forecast_plan(ewma, hot_lists, cold_lists, *, max_swaps: int,
                  min_heat_ratio: float = 1.5, window=None,
                  prior_weight: float = 0.25):
    """Predict the next epoch's plan: fold the ROLLING probe window
    (the traffic accumulated since the last epoch — a read-only peek
    of the ledger, so the epoch's claim still sees every probe) with
    the per-epoch drift EWMA (the history prior that keeps a sparse
    partial window from whipsawing the forecast), then run the SAME
    :func:`~raft_tpu.serving.placement.plan_epoch` policy over the
    folded counts (scaled to integers — the policy compares ratios,
    so a common scale changes nothing) against the current
    assignment. The EWMA enters DOWN-WEIGHTED (``prior_weight``): it
    is a full-epoch-magnitude prior, and on an abrupt drift its stale
    heat on the incumbent hot lists would otherwise swamp the partial
    window and hold the hysteresis ratio shut exactly when the next
    epoch is about to swap. Pure and deterministic; ties break
    exactly like the real epoch, so a correct forecast IS the plan."""
    from raft_tpu.serving.placement import plan_epoch

    counts = np.asarray(ewma, np.float64)
    if window is not None:
        counts = prior_weight * counts + np.asarray(window, np.float64)
    counts = np.rint(counts * 1024.0)
    return plan_epoch(counts.astype(np.int64), hot_lists, cold_lists,
                      max_swaps=max_swaps,
                      min_heat_ratio=min_heat_ratio)


@partial(jax.jit, donate_argnums=(0,))
def _stage_row_fn(staged_plane, cold_plane, cold_slot, row):
    """One background promotion DMA: copy cold list block
    ``cold_slot`` into staged row ``row``. The staged plane is
    DONATED (updates in place — the miss cache must not double its
    HBM while staging); slot and row are traced scalars, so one
    compiled program per plane geometry serves every prefetch — the
    zero-compile discipline the acceptance gate measures."""
    block = jax.device_put(
        jax.lax.dynamic_index_in_dim(cold_plane, cold_slot, 0,
                                     keepdims=False),
        jax.memory.Space.Device)
    return jax.lax.dynamic_update_index_in_dim(staged_plane, block,
                                               row, 0)


class TierPrefetcher:
    """The graftcast background promotion channel for one tiered
    container (any :class:`~raft_tpu.neighbors.tiered._TieredPlanes`
    family — flat, PQ, or BQ; the staged planes mirror the
    container's ``_PLANE_PAIRS`` hot geometry).

    Driven entirely by the :class:`~raft_tpu.serving.placement
    .TierManager`: :meth:`observe` folds each epoch's claimed window
    (under the manager's epoch lock — the window is claimed ONCE and
    feeds plan and forecast from the same read), :meth:`prefetch`
    stages predicted promotions at the lead-time tick, :meth:`take`
    hands staged rows to ``apply_plan`` at the epoch. A ``width=0``
    or capacity-refused prefetcher is DISABLED: every method is a
    cheap no-op and serving is exactly the reactive PR 14 path.
    """

    def __init__(self, tiered, *, width: int,
                 config: Optional[PrefetchConfig] = None,
                 ledger: Optional[object] = None):
        self.tiered = tiered
        self.config = config or PrefetchConfig()
        self.ledger = ledger
        self._lock = threading.Lock()
        self._ewma = np.zeros((tiered.n_lists,), np.float64)  # guarded-by: _lock
        self._epochs_observed = 0                             # guarded-by: _lock
        self._stage_seq = 0                                   # guarded-by: _lock
        cap = self.config.capacity
        if cap is None:
            cap = int(width)
        cap = max(0, min(int(cap), tiered.n_cold))
        led = self._ledger()
        if led is not None and cap > 0:
            headroom = led.headroom_bytes()
            if headroom is not None:
                usable = max(
                    float(headroom)
                    * (1.0 - self.config.safety_fraction), 0.0)
                cap = min(cap, int(usable // max(tiered.block_bytes,
                                                 1)))
        self.capacity = cap
        # row bookkeeping (host-side truth): which list each staged
        # row holds (−1 free), the placement generation it was staged
        # against, and a logical age for LRU eviction
        self._row_list = np.full((cap,), -1, np.int64)  # guarded-by: _lock
        self._row_gen = np.zeros((cap,), np.int64)      # guarded-by: _lock
        self._row_age = np.zeros((cap,), np.int64)      # guarded-by: _lock
        # fixed (K, ...) staged storage per hot plane, committed to
        # the default device like the hot tier it feeds — allocated
        # ONCE; every stage donates it back in place
        self.planes: Dict[str, jax.Array] = {}
        if cap > 0:
            dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
            self.planes = jax.device_put(
                {hot_name: jnp.zeros(
                    (cap,) + tuple(getattr(tiered, hot_name).shape[1:]),
                    getattr(tiered, hot_name).dtype)
                 for hot_name, _ in type(tiered)._PLANE_PAIRS}, dev)

    # -- wiring ---------------------------------------------------------------

    def _ledger(self):
        """The capacity authority: an explicitly attached ledger wins,
        else the process-wide armed gate (so ``install_gate`` covers
        prefetch exactly like build/extend admission)."""
        return self.ledger if self.ledger is not None \
            else memwatch.gate()

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    # -- forecast -------------------------------------------------------------

    def observe(self, window_counts) -> None:
        """Fold one CLAIMED epoch window into the traffic EWMA.
        Called by the TierManager inside its epoch critical section —
        the same single claim feeds the epoch plan and this forecast,
        so a racing scrape can never double-fold a window (the
        DriftDetector locking model)."""
        window = np.asarray(window_counts, np.float64)
        a = self.config.alpha
        with self._lock:
            expect(window.shape == self._ewma.shape,
                   "observe() needs one count per list")
            if self._epochs_observed == 0:
                self._ewma = window.copy()
            else:
                self._ewma = a * window + (1.0 - a) * self._ewma
            self._epochs_observed += 1

    def predict(self, *, max_swaps: int, window=None):
        """The next-epoch plan forecast from the rolling window (the
        TierManager's read-only peek at the traffic since the last
        epoch) + the EWMA prior, against the container's CURRENT
        assignment (snapshotted under its swap lock so a concurrent
        epoch can't tear hot/cold)."""
        with self._lock:
            ewma = self._ewma.copy()
        with self.tiered._swap_lock:
            hot = self.tiered.hot_lists.copy()
            cold = self.tiered.cold_lists.copy()
        return forecast_plan(ewma, hot, cold, max_swaps=max_swaps,
                             min_heat_ratio=self.config.min_heat_ratio,
                             window=window,
                             prior_weight=self.config.prior_weight)

    # -- the background channel -----------------------------------------------

    def prefetch(self, *, max_swaps: int, window=None) -> int:
        """Stage the forecast promotions' cold blocks into the miss
        cache, ahead of the epoch. Returns the number of stage DMAs
        issued. Capacity-refused staging (the ledger gate says the
        active bytes would not fit) degrades to the reactive path:
        the remaining predictions are cancelled (counted), nothing
        raises toward serving."""
        if not self.enabled:
            return 0
        plan = self.predict(max_swaps=max_swaps, window=window)
        if not plan.promotions:
            return 0
        from raft_tpu.neighbors.tiered import _slot_maps

        issued = cancelled = 0
        pair_map = dict(type(self.tiered)._PLANE_PAIRS)
        with self._lock:
            # host mirrors under the swap lock — the slot truth
            # without fetching the device maps (R5: the prefetch
            # path never syncs on an array)
            with self.tiered._swap_lock:
                gen = self.tiered.generation
                _, cold_map = _slot_maps(self.tiered.hot_lists,
                                         self.tiered.cold_lists,
                                         self.tiered.n_lists)
            for lid in plan.promotions:
                if self._find_row_locked(lid, gen) >= 0:
                    continue                     # already staged, fresh
                cs = int(cold_map[lid])
                if cs < 0:
                    continue                     # promoted meanwhile
                row = self._free_row_locked()
                if row < 0:
                    row = self._evict_lru_locked()
                    cancelled += 1
                try:
                    self._admit_locked(extra_rows=1)
                except CapacityExceeded:
                    # degrade to reactive: free the row we grabbed,
                    # count the refusal, stop staging this round —
                    # the epoch will stream these from cold as before
                    self._row_list[row] = -1
                    cancelled += 1
                    break
                for hot_name in self.planes:
                    cold_plane = getattr(self.tiered,
                                         pair_map[hot_name])
                    self.planes[hot_name] = _stage_row_fn(
                        self.planes[hot_name], cold_plane,
                        jnp.int32(cs), jnp.int32(row))
                self._stage_seq += 1
                self._row_list[row] = int(lid)
                self._row_gen[row] = gen
                self._row_age[row] = self._stage_seq
                issued += 1
        if issued:
            tracing.inc_counter(ISSUED, float(issued))
        if cancelled:
            tracing.inc_counter(CANCELLED, float(cancelled))
        return issued

    def take(self, promotions, generation: int) -> Optional[StagedBlocks]:
        """Resolve one epoch's promotions against the miss cache:
        rows staged for these lists AT this placement generation are
        hits (consumed — ``apply_plan`` mixes them in and the rows
        free), everything else is a miss and streams from cold. Rows
        staged against an OLDER generation are stale — the epoch (or
        a re-demotion) moved the placement under them — and are
        cancelled, never served: bit-stability beats byte savings."""
        if not self.enabled:
            return None
        rows = np.full((len(promotions),), -1, np.int32)
        hits = stale = 0
        with self._lock:
            # retire stale rows first so a stale stage can never hit
            old = (self._row_list >= 0) & (self._row_gen
                                           != int(generation))
            stale = int(old.sum())
            self._row_list[old] = -1
            for i, lid in enumerate(promotions):
                r = self._find_row_locked(int(lid), int(generation))
                if r >= 0:
                    rows[i] = r
                    self._row_list[r] = -1       # consumed
                    hits += 1
            self._release_locked()
        misses = len(promotions) - hits
        tracing.inc_counters({HITS: float(hits),
                              MISSES: float(misses)})
        if stale:
            tracing.inc_counter(CANCELLED, float(stale))
        if hits == 0:
            return None
        return StagedBlocks(rows=rows, planes=dict(self.planes))

    def maintain(self) -> int:
        """Miss-cache eviction under shrinking headroom: while the
        ACTIVE staged bytes exceed what the ledger's current headroom
        sustains (headroom already excludes this prefetcher's own
        hold), evict least-recently-staged rows and shrink the hold.
        Returns rows evicted (counted ``tier.prefetch.cancelled``)."""
        led = self._ledger()
        if not self.enabled or led is None:
            return 0
        evicted = 0
        with self._lock:
            headroom = led.headroom_bytes()
            if headroom is None:
                return 0
            block = max(int(self.tiered.block_bytes), 1)
            allowance = max(
                (float(headroom) + self._active_bytes_locked())
                * (1.0 - self.config.safety_fraction), 0.0)
            budget_rows = int(allowance // block)
            while int((self._row_list >= 0).sum()) > budget_rows:
                self._evict_lru_locked()
                evicted += 1
            self._release_locked()
        if evicted:
            tracing.inc_counter(CANCELLED, float(evicted))
        return evicted

    # -- row bookkeeping (all under self._lock) -------------------------------

    def _find_row_locked(self, lid: int, gen: int) -> int:
        m = np.nonzero((self._row_list == lid)
                       & (self._row_gen == gen))[0]
        return int(m[0]) if m.size else -1

    def _free_row_locked(self) -> int:
        m = np.nonzero(self._row_list < 0)[0]
        return int(m[0]) if m.size else -1

    def _evict_lru_locked(self) -> int:
        live = np.nonzero(self._row_list >= 0)[0]
        if not live.size:
            return -1
        row = int(live[np.argmin(self._row_age[live])])
        self._row_list[row] = -1
        return row

    def _active_bytes_locked(self) -> int:
        return int((self._row_list >= 0).sum()) \
            * int(self.tiered.block_bytes)

    def _admit_locked(self, extra_rows: int = 0) -> None:
        """Grow the ledger hold to cover the active rows plus
        ``extra_rows`` about to stage — THE capacity-gate touchpoint:
        :class:`CapacityExceeded` propagates to :meth:`prefetch`'s
        degrade path, so a prefetch can never OOM what serving
        needs."""
        led = self._ledger()
        if led is None or not hasattr(led, "reserve"):
            return
        led.reserve("tier.prefetch", self._active_bytes_locked()
                    + extra_rows * int(self.tiered.block_bytes))

    def _release_locked(self) -> None:
        led = self._ledger()
        if led is None or not hasattr(led, "reserve"):
            return
        led.reserve("tier.prefetch", self._active_bytes_locked())

    # -- scrape surface -------------------------------------------------------

    def snapshot(self) -> dict:
        """The ``/tier.json`` ``prefetch`` block."""
        with self._lock:
            staged = int((self._row_list >= 0).sum())
            return {
                "enabled": self.enabled,
                "capacity": int(self.capacity),
                "staged": staged,
                "staged_bytes": self._active_bytes_locked(),
                "epochs_observed": int(self._epochs_observed),
                "config": {
                    "alpha": self.config.alpha,
                    "safety_fraction": self.config.safety_fraction,
                    "min_heat_ratio": self.config.min_heat_ratio,
                    "prior_weight": self.config.prior_weight,
                },
            }
