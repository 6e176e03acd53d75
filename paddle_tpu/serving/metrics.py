"""Serving metrics: tokens/s, slot occupancy, queue wait, time-to-first-
token, and compile (trace) counts for the continuous-batching engine.

Reporting rides the existing fluid/profiler.py machinery: wall-clock
spans land in an OpCostCollector (the same rows `with profiler(...)`
prints — Event/Calls/Total/Min/Max/Ave in ms) and `print_report()`
renders through profiler._print_table, so serving output reads exactly
like a training profile. Aggregates (`report()`) carry the
offline-measurable numbers the PERF.md serving section cites: mean slot
occupancy and per-bucket compile counts are deterministic on any
backend; tokens/s is only meaningful on-chip.
"""

from __future__ import annotations

import collections
import logging
import time
from typing import Dict

from jax.profiler import TraceAnnotation

__all__ = ["ServingMetrics"]

_LOG = logging.getLogger(__name__)


# A long-lived engine records one value per decode step / per request
# forever — growing a Python float list without bound is the same trap
# the executor's CompileCache closes for compiled entries, so aggregates
# are running sums, not history. The accumulator lives in utils.stat
# (shared with data.DataMetrics); the underscore alias is the
# backward-compatible name.
from ..utils.stat import RunningStat as _RunningStat


class _Phase(object):
    """One open span of `ServingMetrics.phase`: a profiler annotation
    around a host-clock pair. `t0`/`t1` stay readable after the block
    (the engine's device-busy union reads them)."""

    __slots__ = ("_metrics", "_ann", "name", "row", "t0", "t1")

    def __init__(self, metrics, name, row, attrs):
        self._metrics, self.name, self.row = metrics, name, row
        self._ann = TraceAnnotation(name, **attrs)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        self._ann.__exit__(*exc)
        self._metrics._fold(self)
        return False


class ServingMetrics(object):
    def __init__(self, max_slots: int):
        from ..fluid.profiler import OpCostCollector

        self.max_slots = int(max_slots)
        self.ops = OpCostCollector()  # wall-clock spans, profiler rows
        # fn-name -> times TRACED (a retrace == a recompile; the static
        # shape discipline the engine depends on makes these O(1))
        self.trace_counts: Dict[str, int] = {}
        self.prefills = 0
        self.decode_steps = 0
        self.tokens_out = 0
        self.occupancy = _RunningStat()  # live slots / max_slots per decode
        self.queue_wait_s = _RunningStat()  # submit -> admission
        self.ttft_s = _RunningStat()  # submit -> first token
        # PR 4 counters — same O(1) discipline (ints + RunningStat, no
        # per-request lists): chunked-prefill work actually computed,
        # prefix-pool reuse per admission, and side-band h2d uploads
        # (the steady decode loop must not grow this)
        self.prefill_chunks = 0
        self.prefill_tokens_computed = 0
        self.band_uploads = 0
        self.prefix_hit_tokens = _RunningStat()  # cached tokens/admission
        self.prefix_cache = None  # set by the engine when reuse is on
        # PR 12: set by the engine when the paged LoRA adapter pool is
        # on — report() surfaces its O(1) hit/miss/eviction/upload
        # counters (serving/adapters.py)
        self.adapter_pool = None
        # PR 15: set by the engine when KV block fingerprints are on —
        # report() surfaces the commit/verify/mismatch counters
        # (serving/integrity.py BlockFingerprints)
        self.block_fp = None
        # PR 7 counters — paged KV block pool + speculative decoding,
        # same O(1) discipline. Gauges (set by the engine each step or
        # scheduler event) vs cumulative ints are marked below.
        self.kv_blocks_total = 0          # gauge: pool size in blocks
        self.kv_blocks_in_use = 0         # gauge: physical blocks live
        self.kv_frag_tokens = 0           # gauge: allocated - resident
        self.kv_blocks_freed_at_retire = 0  # cumulative physical frees
        self.kv_tail_blocks_freed = 0     # cumulative: reserved, never
        #                                   reached (early EOS tails)
        self.cow_blocks = 0               # cumulative copy-on-writes
        # ISSUE 27 counters — a hybrid family's three caches (None /
        # zero for the GPT block): blocks freed behind the attention
        # window, slots whose recurrent state was zeroed at admission,
        # bytes resident by kind of cache (gauge), and per decode step
        # those bytes over the live slots
        self.window_blocks_released = 0   # cumulative
        self.state_slots_reset = 0        # cumulative
        self.cache_bytes_in_use = None    # gauge: {"full", "window", "state"}
        self.cache_bytes_per_slot = _RunningStat()
        # ISSUE 33 counters — a family with routed experts computes them
        # on the device and the decode step's packed result carries
        # them (`step_counters` of the family's seam; empty otherwise):
        # per decode step, the distinct experts its live rows reached,
        # summed over the expert layers, and the fullest expert's rows
        self.moe_experts_hit = _RunningStat()
        self.moe_rows_max = _RunningStat()
        self.spec_windows = 0             # cumulative verify rows run
        self.spec_drafted = 0             # cumulative drafted tokens
        self.spec_accepted = 0            # cumulative drafts emitted
        # PR 8 counters — request-SLO layer (deadlines, gray-failure
        # demotion, token-level resume), same O(1) discipline.
        self.expired = 0                  # cumulative deadline verdicts
        self.cancelled = 0                # cumulative fleet cancels
        self.resumed_requests = 0         # cumulative token-level resumes
        self.resume_tokens_reused = 0     # cumulative tokens NOT re-decoded
        # EWMA of ServingEngine.step() wall time (gauge; includes the
        # injector tick, so an injected gray stall is visible here —
        # that is the point: this gauge feeds the fleet's slow-replica
        # health score). 0.0 until the first step.
        self.step_ewma_s = 0.0
        # PR 13 gauge — which paged-attention kernel the engine's
        # compiled steps were traced with ("fused" Pallas table-walk or
        # "gather" XLA view; set once at engine construction)
        self.paged_kernel = None
        # PR 14 gauges — the KV pool's storage dtype ("none" | "int8"
        # | "fp8") and the weight storage ("int8" | None), both fixed
        # at engine construction; the fleet's per-replica stats rows
        # surface them (a mixed-quant fleet is refused at spawn, so
        # these also double as the audit trail for that invariant)
        self.kv_quant = None
        self.weight_quant = None
        # PR 11 gauge — the weight version this engine serves (the
        # fleet's live-rollout version fence stamps it at engine
        # construction; None outside a versioned fleet). A gauge like
        # kv_blocks_in_use: a dead incarnation's version says nothing
        # about its replacement.
        self.weights_version = None
        # PR 16 counters — durable KV tier, same O(1) discipline.
        # Cumulative ints; the fleet's per-replica stats rows sum them
        # across incarnations like the fingerprint counters.
        self.tokens_recomputed_at_migration = 0  # cumulative: closed-
        #                                   block prompt tokens a
        #                                   resumed admission re-
        #                                   prefilled (0 == clean path)
        self.handoff_imports = 0          # cumulative clean imports
        self.handoff_blocks_imported = 0  # cumulative blocks imported
        self.handoff_tokens_imported = 0  # cumulative tokens imported
        self.handoff_fallbacks = 0        # cumulative re-prefill falls
        self.store_spilled_blocks = 0     # cumulative publish spills
        self.store_warm_blocks = 0        # cumulative warm-start loads
        self.store_quarantined = 0        # cumulative fp-reject loads
        # PR 16: set by the engine when a durable KV store is attached
        # — report() surfaces its record/byte/quarantine counters
        # (serving/kv_store.py KVBlockStore)
        self.kv_store = None
        # PR 28 — how often the engine keeps the chip one decode step
        # ahead of the host (cumulative): steps dispatched BEFORE their
        # predecessor was read, and steps where a host event (admission,
        # cancel, expiry, a divergence) made the engine read first. Over
        # `decode_steps`, the first is the share of steps run ahead.
        self.decode_dispatched_ahead = 0
        self.decode_chain_breaks = 0
        # PR 25 — the scheduler's own phases (`phase()` below):
        # seconds by phase name since the engine last cleared it (at
        # the top of every step()), the count of step() calls, and the
        # last few steps slower than SLOW_STEP_S with their phase
        # seconds — what a stall in an untraced run leaves behind
        self.steps = 0
        self.step_phases: Dict[str, float] = {}
        self.slow_steps = collections.deque(maxlen=8)
        self._t0 = None
        self._t1 = None

    STEP_EWMA_ALPHA = 0.5  # fast decay: ~3 healthy steps erase a spike
    SLOW_STEP_S = 0.5  # a step() slower than this is kept and logged

    def observe_step(self, seconds: float):
        """Fold one engine-step wall time into the step-latency EWMA
        (the fleet's gray-failure score compares this gauge across
        replicas)."""
        a = self.STEP_EWMA_ALPHA
        if seconds > self.SLOW_STEP_S:
            # which phase held the step: time of `engine.step` that no
            # phase beneath it accounts for is the scheduler's own
            # Python (or a stall of the whole process)
            rec = {"step": self.steps, "seconds": round(seconds, 6),
                   "phases": {k: round(v, 6)
                              for k, v in self.step_phases.items()}}
            self.slow_steps.append(rec)
            _LOG.warning("slow engine step: %r", rec)
        if self.step_ewma_s == 0.0:
            self.step_ewma_s = seconds
        else:
            self.step_ewma_s = a * seconds + (1.0 - a) * self.step_ewma_s

    # -- recording ------------------------------------------------------
    def count_trace(self, name: str):
        """Called from INSIDE the traced functions: runs once per trace
        (== once per compile signature), never per execution."""
        self.trace_counts[name] = self.trace_counts.get(name, 0) + 1

    def phase(self, name: str, row: str = None, **attrs):
        """Context manager around one phase of the scheduler's work —
        the engine's one timing mechanism, with two sinks. Under a
        running `jax.profiler` trace the span lies on the host plane
        of the same `.xplane.pb` as the device's operations, on their
        clock (`attrs` — a request id, a bucket — ride as the
        annotation's arguments: the NAME stays one of a dozen fixed
        `engine.<phase>` strings). With no trace running it costs two
        clock reads and folds, O(1), into the `ops` row `name` (and
        `row`, the row's older name where it had one) and into
        `step_phases`."""
        return _Phase(self, name, row, attrs)

    def _fold(self, ph: _Phase):
        seconds = ph.t1 - ph.t0
        self.ops.record(ph.name, seconds)
        if ph.row is not None:
            self.ops.record(ph.row, seconds)
        self.step_phases[ph.name] = \
            self.step_phases.get(ph.name, 0.0) + seconds
        if self._t0 is None:
            self._t0 = ph.t0
        self._t1 = ph.t1

    # -- derived --------------------------------------------------------
    @property
    def wall_s(self) -> float:
        if self._t0 is None:
            return 0.0
        return (self._t1 or self._t0) - self._t0

    def prefill_trace_count(self) -> int:
        return sum(
            n for k, n in self.trace_counts.items() if k.startswith("prefill")
        )

    def decode_trace_count(self) -> int:
        return self.trace_counts.get("decode_step", 0)

    def report(self) -> dict:
        def _mean(st):
            return round(st.mean, 6) if st.count else None

        wall = self.wall_s
        rep = {
            "tokens_out": self.tokens_out,
            "tokens_per_sec": round(self.tokens_out / wall, 2) if wall else None,
            "decode_steps": self.decode_steps,
            "decode_dispatched_ahead": self.decode_dispatched_ahead,
            "decode_chain_breaks": self.decode_chain_breaks,
            "prefills": self.prefills,
            "mean_occupancy": _mean(self.occupancy),
            "mean_queue_wait_s": _mean(self.queue_wait_s),
            "max_queue_wait_s": round(self.queue_wait_s.max, 6)
            if self.queue_wait_s.count else None,
            "mean_ttft_s": _mean(self.ttft_s),
            "compile_counts": dict(self.trace_counts),
            "prefill_traces": self.prefill_trace_count(),
            "decode_traces": self.decode_trace_count(),
            "wall_s": round(wall, 4),
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "band_uploads": self.band_uploads,
            "mean_prefix_hit_tokens": _mean(self.prefix_hit_tokens),
            "kv_blocks_total": self.kv_blocks_total,
            "kv_blocks_in_use": self.kv_blocks_in_use,
            "kv_frag_tokens": self.kv_frag_tokens,
            "kv_blocks_freed_at_retire": self.kv_blocks_freed_at_retire,
            "kv_tail_blocks_freed": self.kv_tail_blocks_freed,
            "cow_blocks": self.cow_blocks,
            "window_blocks_released": self.window_blocks_released,
            "state_slots_reset": self.state_slots_reset,
            "cache_bytes_in_use": self.cache_bytes_in_use,
            "mean_moe_experts_hit": _mean(self.moe_experts_hit),
            "mean_moe_rows_max": _mean(self.moe_rows_max),
            "spec_windows": self.spec_windows,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_accept_rate": round(
                self.spec_accepted / self.spec_drafted, 4)
            if self.spec_drafted else None,
            "expired": self.expired,
            "cancelled": self.cancelled,
            "resumed_requests": self.resumed_requests,
            "resume_tokens_reused": self.resume_tokens_reused,
            "step_ewma_s": round(self.step_ewma_s, 6),
            "paged_kernel": self.paged_kernel,
            "kv_quant": self.kv_quant,
            "weight_quant": self.weight_quant,
            "weights_version": self.weights_version,
            "tokens_recomputed_at_migration":
                self.tokens_recomputed_at_migration,
            "handoff_imports": self.handoff_imports,
            "handoff_blocks_imported": self.handoff_blocks_imported,
            "handoff_tokens_imported": self.handoff_tokens_imported,
            "handoff_fallbacks": self.handoff_fallbacks,
            "store_spilled_blocks": self.store_spilled_blocks,
            "store_warm_blocks": self.store_warm_blocks,
            "store_quarantined": self.store_quarantined,
            "steps": self.steps,
            "slow_steps": list(self.slow_steps),
        }
        if self.prefix_cache is not None:
            rep["prefix_cache"] = self.prefix_cache.stats()
        if self.adapter_pool is not None:
            rep["adapter_pool"] = self.adapter_pool.stats()
        if self.block_fp is not None:
            rep["block_fingerprints"] = self.block_fp.stats()
        if self.kv_store is not None:
            rep["kv_store"] = self.kv_store.stats()
        return rep

    def table(self, sorted_key="total"):
        return self.ops.table(sorted_key)

    def print_report(self):
        from ..fluid.profiler import _print_table

        _print_table(self.table(), self.wall_s)
