"""Serving integrity sentinel (ISSUE 15): silent-corruption detection,
known-answer canaries, and taint-aware journal resume.

Five layers of coverage:

  1. In-step numeric traps — non-finite logits become an IntegrityError
     instead of an emitted token, with the trap reduction FOLDED into
     the one compiled decode/verify/chunk step (compile-count pinned:
     decode still traced exactly once).
  2. KV block fingerprints — committed at publish, spot-verified on
     aliased re-open (the flip@ drill trips there), dropped when a
     block is freed (recycled ids are never judged against a previous
     tenant's checksum).
  3. Known-answer canaries + quarantine — clean canaries advance the
     taint base; a garbled replica's canary mismatch quarantines it
     exactly once (fresh incarnation), with outputs token-identical to
     an uninjected run (zero tainted tokens survive).
  4. Taint-aware journal — `RequestJournal.integrity` truncates the
     mirror to the verified prefix, rides replay/compaction/
     recover_progress, and the DFA's J010 taint fence audits that ONLY
     tainted tokens ever re-decode (corpus tests per violation shape).
  5. The shared detector core — `utils.detector.TripDetector` is ONE
     implementation behind both the training DivergenceDetector and
     the serving sentinel (ISSUE 15 satellite).
"""

import json
import os
import tempfile
import time

import numpy as np
import pytest

import bench

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.analysis.protocol_lint import (  # noqa: E402
    verify_journal,
    verify_records,
)
from paddle_tpu.distributed.fault_injection import FaultInjector  # noqa: E402
from paddle_tpu.distributed.sentinel import DivergenceDetector  # noqa: E402
from paddle_tpu.models import transformer as tlm  # noqa: E402
from paddle_tpu.serving import (  # noqa: E402
    IntegrityError,
    RequestJournal,
    ServingEngine,
    ServingFleet,
    ServingSentinel,
    golden_trace,
)
from paddle_tpu.utils.detector import TripDetector  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return tlm.TransformerConfig(vocab=32, dim=16, heads=2, layers=2,
                                 max_len=64, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(cfg):
    return tlm.init_params(cfg, jax.random.PRNGKey(0))


def _gen(params, cfg, prompt, n):
    return list(np.asarray(
        tlm.generate(params, np.asarray(prompt, np.int32)[None, :],
                     cfg, n))[0])


PROMPT = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)


# ---------------------------------------------------------------------
# 5. the shared detector core (refactor satellite)
# ---------------------------------------------------------------------

def test_divergence_detector_is_the_shared_trip_core():
    # ONE hysteresis implementation: the training detector subclasses
    # utils.detector.TripDetector (behavior pinned by the existing
    # sentinel suite), and the serving sentinel instantiates it
    assert issubclass(DivergenceDetector, TripDetector)
    s = ServingSentinel(spike_factor=4.0)
    assert isinstance(s.detector, TripDetector)


def test_serving_sentinel_verdicts():
    s = ServingSentinel(spike_factor=4.0, hysteresis=2, warmup=2)
    assert s.observe(True, 1.0) == "trap"        # hard verdict
    for _ in range(4):
        assert s.observe(False, 1.0) == "ok"     # EWMA seeds
    assert s.observe(False, 50.0) == "ok"        # within hysteresis
    assert s.observe(False, 50.0) == "spike"     # sustained excursion
    # spike detection off (the default): magnitude never trips
    s2 = ServingSentinel()
    for v in (1.0, 1e6, 1e12):
        assert s2.observe(False, v) == "ok"


# ---------------------------------------------------------------------
# 1. in-step numeric traps
# ---------------------------------------------------------------------

def test_trap_on_nonfinite_logits_instead_of_a_token(params, cfg):
    bad = jax.tree_util.tree_map(lambda x: x, params)
    bad["embed"] = params["embed"].at[int(PROMPT[-1])].set(jnp.nan)
    eng = ServingEngine(bad, cfg, max_slots=2)
    h = eng.submit(PROMPT, 4)
    with pytest.raises(IntegrityError) as ei:
        h.result()
    assert ei.value.kind == "trap"
    assert h.tokens == []  # the tripped slot emitted NOTHING
    # the engine is latched (EngineFailed wrapping the trip): a
    # half-donated cache is never re-stepped, and the IntegrityError
    # stays reachable as the cause — the fleet's _on_crash unwraps it
    from paddle_tpu.serving import EngineFailed
    with pytest.raises(EngineFailed) as e2:
        eng.step()
    assert isinstance(e2.value.__cause__, IntegrityError)
    assert h.error is not None  # pending handles carry the failure


def test_traps_fold_into_the_one_compiled_decode(params, cfg):
    # traps ON (the default) change neither outputs nor trace counts:
    # decode is still compiled exactly once, prefill <= buckets, and
    # greedy output stays token-identical to sequential generate()
    eng = ServingEngine(params, cfg, max_slots=2)
    assert eng.integrity_traps
    out = list(eng.submit(PROMPT, 6).result())
    assert out == _gen(params, cfg, PROMPT, 6)
    assert eng.metrics.decode_trace_count() == 1
    # second wave retraces nothing
    out2 = list(eng.submit(PROMPT, 6).result())
    assert out2 == out
    assert eng.metrics.decode_trace_count() == 1


def test_traps_fold_into_the_spec_verify_step(params, cfg):
    eng = ServingEngine(params, cfg, max_slots=2, spec_draft_len=3)
    out = list(eng.submit(PROMPT, 6).result())
    assert out == _gen(params, cfg, PROMPT, 6)
    assert eng.metrics.trace_counts.get("spec_verify") == 1


def test_traps_off_knob(params, cfg):
    eng = ServingEngine(params, cfg, max_slots=2, integrity_traps=False)
    out = list(eng.submit(PROMPT, 6).result())
    assert out == _gen(params, cfg, PROMPT, 6)


def test_spike_knob_validation(params, cfg):
    with pytest.raises(ValueError):
        ServingEngine(params, cfg, integrity_spike_factor=0.5)
    # the spike detector rides the trap reduction's scalar: asking for
    # it with traps off would be silently dead — refused loudly
    with pytest.raises(ValueError, match="integrity_traps"):
        ServingEngine(params, cfg, integrity_traps=False,
                      integrity_spike_factor=4.0)
    # fingerprints commit at publish / verify at aliased re-open:
    # without a prefix cache neither audit point exists — refused
    # loudly rather than silently dead
    with pytest.raises(ValueError, match="prefix_cache_tokens"):
        ServingEngine(params, cfg, kv_fingerprints=True)


# ---------------------------------------------------------------------
# 2. KV block fingerprints
# ---------------------------------------------------------------------

FP_KW = dict(max_slots=2, kv_block_tokens=4, prefix_cache_tokens=64,
             kv_fingerprints=True)


def test_fingerprints_commit_at_publish_verify_at_alias(params, cfg):
    eng = ServingEngine(params, cfg, **FP_KW)
    ref = _gen(params, cfg, PROMPT, 6)
    assert list(eng.submit(PROMPT, 6).result()) == ref
    assert eng._fp.committed == len(PROMPT) // 4  # whole prompt blocks
    assert eng._fp.verified == 0
    # a DIFFERENT request re-opens the published blocks: spot-verified
    assert list(eng.submit(PROMPT, 6).result()) == ref
    assert eng._fp.verified >= 1 and eng._fp.mismatches == 0
    # the fingerprint reduction is jitted ONCE
    assert eng.metrics.trace_counts.get("block_fp") == 1
    assert eng.metrics.report()["block_fingerprints"]["mismatches"] == 0


def test_flip_fault_trips_fingerprint_on_aliased_reopen(params, cfg):
    inj = FaultInjector("")
    eng = ServingEngine(params, cfg, fault_injector=inj, **FP_KW)
    eng.submit(PROMPT, 6).result()      # publish + fingerprint
    inj.arm("flip@1")                   # corrupt a resident block
    with pytest.raises(IntegrityError) as ei:
        eng.submit(PROMPT, 6).result()  # aliased re-open spot-check
    assert ei.value.kind == "fingerprint"
    assert eng._fp.mismatches == 1


def test_fingerprint_dropped_when_block_is_freed(params, cfg):
    # a tiny trie budget forces eviction: the evicted block's
    # fingerprint must drop with it, so the recycled physical id is
    # never judged against the previous tenant's checksum
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=4,
                        prefix_cache_tokens=8, kv_fingerprints=True,
                        kv_pool_blocks=8)
    p2 = np.array([7, 7, 8, 8, 9, 9, 1, 2], np.int32)
    for p in (PROMPT, p2, PROMPT, p2):
        out = list(eng.submit(p, 4).result())
        assert out == _gen(params, cfg, p, 4)
    assert eng._fp.mismatches == 0
    assert eng.prefix_cache.evictions >= 1


def test_flip_with_fingerprints_off_is_silent(params, cfg):
    # the honest negative: without fingerprints the flip is exactly
    # the silent corruption the README warns about — outputs diverge
    # and nothing raises (the canary/fingerprint knobs exist because
    # the traps cannot see finite garbage)
    inj = FaultInjector("")
    eng = ServingEngine(params, cfg, max_slots=2, kv_block_tokens=4,
                        prefix_cache_tokens=64, fault_injector=inj)
    ref = _gen(params, cfg, PROMPT, 6)
    assert list(eng.submit(PROMPT, 6).result()) == ref
    inj.arm("flip@1")
    out = list(eng.submit(PROMPT, 6).result())  # no raise
    assert out != ref  # the corruption really happened


# ---------------------------------------------------------------------
# 3. canaries + quarantine (fleet)
# ---------------------------------------------------------------------

def _fleet_kw(jpath, kw_for=None, canary_s=0.05):
    return dict(n_replicas=2, journal_path=jpath,
                heartbeat_timeout_s=120.0, monitor_interval_s=0.02,
                canary_interval_s=canary_s, auto_refill=True,
                engine_kw={"max_slots": 4, "kv_block_tokens": 4},
                engine_kw_for=kw_for)


def test_canary_knob_validation(params, cfg):
    with pytest.raises(ValueError):
        ServingFleet(params, cfg, canary_interval_s=0.0)
    # a scripted engine cannot derive a golden trace
    from paddle_tpu.analysis.sched_explore import ScriptEngine
    with pytest.raises(ValueError, match="canary_golden"):
        ServingFleet(params, cfg, canary_interval_s=0.1,
                     engine_factory=ScriptEngine)
    # a quantized fleet is not token-identical to generate()
    with pytest.raises(ValueError, match="canary_golden"):
        ServingFleet(params, cfg, canary_interval_s=0.1,
                     engine_kw={"kv_quant": "int8"})


def test_golden_trace_matches_engine_greedy(params, cfg):
    golden = golden_trace(params, cfg, tuple(PROMPT), 5)
    eng = ServingEngine(params, cfg, max_slots=2)
    out = list(eng.submit(PROMPT, 5).result())
    assert out[len(PROMPT):] == golden


def test_clean_canaries_never_trip(params, cfg):
    jpath = tempfile.mktemp(suffix=".jsonl")
    fleet = ServingFleet(params, cfg, **_fleet_kw(jpath))
    try:
        out = list(fleet.submit(PROMPT, 6).result(timeout=300))
        assert out == _gen(params, cfg, PROMPT, 6)
        deadline = time.monotonic() + 60
        while fleet.stats()["canaries_ok"] < 2:
            assert time.monotonic() < deadline, fleet.stats()
            time.sleep(0.02)
        st = fleet.stats()
        assert st["integrity_trips"] == 0
        assert st["canary_mismatches"] == 0
        assert st["canaries_sent"] >= st["canaries_ok"] >= 2
    finally:
        fleet.close()
    assert verify_journal(jpath, expect_closed=True) == []
    os.unlink(jpath)


def test_garble_quarantine_drill_token_identity(params, cfg):
    """The acceptance drill: with garble@ armed on one replica, every
    request completes token-identical to an uninjected fleet, the
    corrupt replica is quarantined EXACTLY once (fresh incarnation via
    the supervisor backoff), and the journal replays green through the
    DFA including J010 — re-decoded tokens lie entirely inside the
    journaled taint window."""
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, 32, rng.randint(4, 9)).astype(np.int32),
             int(rng.randint(24, 40))) for _ in range(5)]
    refs = [_gen(params, cfg, p, n) for p, n in reqs]

    # garble@ from an arbitrary step raced the canary period: a
    # request that completed on the garbled replica before the next
    # canary's verdict was delivered garbled (about one run in six).
    # This injector begins the fault at a step where the canary's
    # verdict is certain to come first (bench.garble_behind_a_canary)
    inj = bench.garble_behind_a_canary()
    armed = {"used": False}

    def kw_for(i):
        # the injector is handed to replica 1 ONCE: the quarantine's
        # fresh incarnation must come up clean, not re-garbled
        if i == 1 and not armed["used"]:
            armed["used"] = True
            return {"fault_injector": inj}
        return {}

    jpath = tempfile.mktemp(suffix=".jsonl")
    fleet = ServingFleet(params, cfg, **_fleet_kw(jpath, kw_for))
    inj.fleet = fleet
    try:
        fleet.submit(*reqs[0]).result(timeout=300)  # warm
        deadline = time.monotonic() + 60
        while fleet.stats()["canaries_ok"] < 2:  # clean mark first
            assert time.monotonic() < deadline, fleet.stats()
            time.sleep(0.02)
        inj.wanted = True
        # waves of the same five requests until a canary has found
        # replica 1 mid-decode and the fault has begun (one wave, as a
        # rule); every request of every wave is held to the reference
        hs = []
        deadline = time.monotonic() + 120
        while not inj.garbled:
            assert time.monotonic() < deadline, fleet.stats()
            wave = [fleet.submit(p, n) for p, n in reqs]
            hs.extend(wave)
            while not inj.garbled and not all(h.done for h in wave):
                time.sleep(0.002)
        outs = [list(h.result(timeout=300)) for h in hs]
        assert outs == refs * (len(hs) // len(reqs))  # zero tainted
        # tokens survive
        deadline = time.monotonic() + 60
        while fleet.stats()["replicas"][1]["incarnation"] < 2:
            assert time.monotonic() < deadline, fleet.stats()
            time.sleep(0.02)
        st = fleet.stats()
        assert st["integrity_trips"] == 1  # quarantined exactly once
        assert st["integrity_trip_kinds"] == {"canary": 1}
        assert st["canary_mismatches"] == 1
        assert st["lost"] == 0
        assert st["replicas"][1]["incarnation"] == 2
    finally:
        fleet.close()
    # the journal DFA (J010 included) is the re-decode auditor: only
    # tainted indices re-decode, nothing lands from the quarantined
    # incarnation after its integrity event
    assert verify_journal(jpath, expect_closed=True) == []
    # and the file really carries the integrity side-band
    kinds = [json.loads(line)["kind"] for line in open(jpath)]
    assert "integrity" in kinds
    os.unlink(jpath)


# ---------------------------------------------------------------------
# 4a. taint-aware journal mechanics
# ---------------------------------------------------------------------

def test_journal_integrity_truncates_mirror_and_survives_replay(tmp_path):
    p = str(tmp_path / "taint.jsonl")
    j = RequestJournal(p)
    j.submit(0, {"max_new_tokens": 6, "eos_id": None})
    j.assign(0, "r1", 1, 0)
    j.progress(0, "r1", 1, 0, [10, 11])
    j.progress(0, "r1", 1, 0, [12, 13])
    # trip: tokens [2, 4) are tainted — the mirror truncates to the
    # verified prefix, so failover resumes from index 2
    j.integrity("r1", 1, {0: (2, 4)}, reason="canary mismatch")
    assert j.progress_of(0) == [10, 11]
    assert j.taint_of(0) == ("r1", 1, 2, 4)
    assert j.lost("r1", 1) == [(0, {"max_new_tokens": 6,
                                    "eos_id": None}, 0, [10, 11])]
    j.close()
    # replay from the file reproduces the truncated mirror
    j2 = RequestJournal(p)
    assert j2.progress_of(0) == [10, 11]
    assert j2.taint_of(0) == ("r1", 1, 2, 4)
    j2.close()
    # the restart helper applies the same truncation
    assert RequestJournal.recover_progress(p) == {0: [10, 11]}


def test_journal_compaction_preserves_taint_side_band(tmp_path):
    p = str(tmp_path / "compact.jsonl")
    j = RequestJournal(p)
    j.submit(0, {"a": 1})
    j.assign(0, "r1", 1, 0)
    j.progress(0, "r1", 1, 0, [10, 11, 12])
    j.integrity("r1", 1, {0: (1, 3)})
    j.submit(1, {"b": 2})  # untainted neighbor
    j.assign(1, "r0", 1, 0)
    assert j.compact()
    # the compacted file still knows the taint window: replaying it
    # reproduces the truncated progress AND the window, and the DFA
    # accepts a re-decode INSIDE it
    j2 = RequestJournal(p)
    assert j2.progress_of(0) == [10]
    assert j2.taint_of(0) == ("r1", 1, 1, 3)
    j2.close()
    recs = [(i + 1, json.loads(line))
            for i, line in enumerate(open(p))]
    assert verify_records(recs) == []
    # post-compaction re-decode inside the preserved window: clean
    recs2 = [r for _, r in recs] + [
        {"kind": "assign", "rid": 0, "replica": "r2", "incarnation": 1,
         "gen": 1},
        {"kind": "progress", "rid": 0, "replica": "r2",
         "incarnation": 1, "gen": 1, "tokens": [21, 22, 23, 24, 25]},
        {"kind": "done", "rid": 0, "replica": "r2", "incarnation": 1,
         "gen": 1, "tokens": [10, 21, 22, 23, 24, 25]},
        {"kind": "rejected", "rid": 1, "reason": "test"},
    ]
    assert verify_records(list(enumerate(recs2, 1)),
                          expect_closed=True) == []
    j.close()


def test_taint_window_consumed_by_redecode(tmp_path):
    # once the survivor's re-decode catches the window back up, the
    # taint is CONSUMED: a later compaction must not re-emit it — a
    # replay re-truncating the survivor's VERIFIED re-decode would
    # discard clean tokens and force a second re-decode on restart
    p = str(tmp_path / "consumed.jsonl")
    j = RequestJournal(p)
    j.submit(0, {"x": 1})
    j.assign(0, "r1", 1, 0)
    j.progress(0, "r1", 1, 0, [10, 11, 12])
    j.integrity("r1", 1, {0: (1, 3)})
    j.assign(0, "r0", 1, 1)
    j.progress(0, "r0", 1, 1, [21, 22])  # re-decode fills [1, 3)
    assert j.taint_of(0) is None          # consumed
    j.progress(0, "r0", 1, 1, [23])       # fresh token past the mark
    assert j.compact()
    j2 = RequestJournal(p)
    # the whole post-truncation history survives the rotation intact
    assert j2.progress_of(0) == [10, 21, 22, 23]
    j2.close()
    assert RequestJournal.recover_progress(p) == {0: [10, 21, 22, 23]}
    kinds = [json.loads(line)["kind"] for line in open(p)]
    assert "integrity" not in kinds  # nothing left to preserve
    j.close()


def test_compaction_mid_redecode_keeps_survivor_tokens(tmp_path):
    # a compaction landing MID-re-decode anchors the emitted window at
    # the CURRENT accumulation (the consolidated progress already
    # reflects the truncation + partial re-decode), so replay
    # truncates nothing and the remaining span stays sanctioned
    p = str(tmp_path / "mid.jsonl")
    j = RequestJournal(p)
    j.submit(0, {"x": 1})
    j.assign(0, "r1", 1, 0)
    j.progress(0, "r1", 1, 0, [10, 11, 12, 13])
    j.integrity("r1", 1, {0: (1, 4)})     # truncate to 1
    j.assign(0, "r0", 1, 1)
    j.progress(0, "r0", 1, 1, [21])       # re-decode reaches 2 of 4
    assert j.compact()
    j2 = RequestJournal(p)
    assert j2.progress_of(0) == [10, 21]  # survivor token KEPT
    assert j2.taint_of(0) == ("r1", 1, 2, 4)  # remaining span
    j2.close()
    recs = [(i + 1, json.loads(line))
            for i, line in enumerate(open(p))]
    assert verify_records(recs) == []
    j.close()


def test_terminal_prunes_taint(tmp_path):
    j = RequestJournal(None)
    j.submit(0, {})
    j.assign(0, "r0", 1, 0)
    j.progress(0, "r0", 1, 0, [1, 2])
    j.integrity("r0", 1, {0: (0, 2)})
    assert j.taint_of(0) is not None
    j.complete(0, "r1", 1, 1, [5, 6])
    assert j.taint_of(0) is None


# ---------------------------------------------------------------------
# 4b. J010 corpus: the taint fence's violation shapes
# ---------------------------------------------------------------------

def _codes(diags):
    return [d.code for d in diags]


def _recs(*records):
    return list(enumerate(records, 1))


S0 = {"kind": "submit", "rid": 0, "spec": {}}
A0 = {"kind": "assign", "rid": 0, "replica": "r1", "incarnation": 1,
      "gen": 0}


def _prog(tokens, replica="r1", inc=1, gen=0, rid=0):
    return {"kind": "progress", "rid": rid, "replica": replica,
            "incarnation": inc, "gen": gen, "tokens": tokens}


def _fin(tokens, replica="r1", inc=1, gen=0, rid=0):
    return {"kind": "done", "rid": rid, "replica": replica,
            "incarnation": inc, "gen": gen, "tokens": tokens}


def _integrity(taint, replica="r1", inc=1):
    return {"kind": "integrity", "replica": replica, "incarnation": inc,
            "taint": {str(r): [f, u] for r, (f, u) in taint.items()}}


def test_j010_clean_taint_resume_is_sanctioned():
    # the fleet's quarantine shape: taint [1, 3), resume from 1 on a
    # new holder, re-decode indices 1..2 INSIDE the window — clean
    diags = verify_records(_recs(
        S0, A0, _prog([10, 11, 12]),
        _integrity({0: (1, 3)}),
        {"kind": "assign", "rid": 0, "replica": "r0", "incarnation": 1,
         "gen": 1},
        _prog([21, 22, 23], replica="r0", gen=1),
        _fin([10, 21, 22, 23], replica="r0", gen=1),
    ), expect_closed=True)
    assert diags == []


def test_j010_redecode_outside_taint_window():
    # "zero re-decode OUTSIDE it": the window says only index [1, 3)
    # of four journaled tokens is tainted, but the survivor's deltas
    # re-cover index 3 too (still below the high-water mark 4) —
    # an untainted, already-journaled token was re-decoded
    diags = verify_records(_recs(
        S0, A0, _prog([10, 11, 12, 13]),
        _integrity({0: (1, 3)}),
        {"kind": "assign", "rid": 0, "replica": "r0", "incarnation": 1,
         "gen": 1},
        _prog([21, 22, 23], replica="r0", gen=1),  # spans [1, 4)
    ))
    assert "J010" in _codes(diags)
    assert any("outside the journaled taint window" in d.message
               for d in diags)
    # the sanctioned shape — deltas stay inside [1, 3), then the
    # request CONTINUES past the high-water mark (fresh indices): clean
    clean = verify_records(_recs(
        S0, A0, _prog([10, 11, 12]),
        _integrity({0: (1, 3)}),
        {"kind": "assign", "rid": 0, "replica": "r0", "incarnation": 1,
         "gen": 1},
        _prog([21, 22], replica="r0", gen=1),   # re-decode [1, 3)
        _prog([24, 25], replica="r0", gen=1),   # fresh [3, 5)
        _fin([10, 21, 22, 24, 25], replica="r0", gen=1),
    ), expect_closed=True)
    assert clean == []


def test_j010_records_from_quarantined_incarnation():
    # "a done whose assignment predates the replica's integrity
    # event": after the integrity record, nothing may land from that
    # (replica, incarnation) — done, progress, or a fresh assign
    base = [S0, A0, _prog([10]), _integrity({0: (0, 1)})]
    done = verify_records(_recs(*base, _fin([10, 11])))
    assert "J010" in _codes(done)
    assert any("quarantined" in d.detail for d in done)
    prog = verify_records(_recs(*base, _prog([11])))
    assert "J010" in _codes(prog)
    assign = verify_records(_recs(
        *base, {"kind": "assign", "rid": 0, "replica": "r1",
                "incarnation": 1, "gen": 1}))
    assert "J010" in _codes(assign)
    # a fresh incarnation of the same replica NAME is a different
    # holder: clean
    fresh = verify_records(_recs(
        *base,
        {"kind": "assign", "rid": 0, "replica": "r1", "incarnation": 2,
         "gen": 1},
        _prog([21], inc=2, gen=1),
        _fin([21], inc=2, gen=1),
    ), expect_closed=True)
    assert fresh == []


def test_j010_ill_formed_taint_windows():
    # unknown rid
    d1 = verify_records(_recs(S0, A0, _integrity({7: (0, 1)})))
    assert "J010" in _codes(d1)
    # window past the journaled progress
    d2 = verify_records(_recs(S0, A0, _prog([10]),
                              _integrity({0: (3, 5)})))
    assert "J010" in _codes(d2)
    # from > upto
    d3 = verify_records(_recs(S0, A0, _prog([10]),
                              _integrity({0: (1, 0)})))
    assert "J010" in _codes(d3)
    # tainting a rid that already has its verdict
    d4 = verify_records(_recs(S0, A0, _prog([10]), _fin([10]),
                              _integrity({0: (0, 1)})))
    assert "J010" in _codes(d4)


def test_integrity_record_typing_is_j008():
    # ill-typed taint map / holder: J008 like any malformed record,
    # never a TypeError out of the DFA
    d1 = verify_records(_recs(
        S0, A0, {"kind": "integrity", "replica": "r1",
                 "incarnation": 1, "taint": {"zero": [0]}}))
    assert "J008" in _codes(d1)
    d2 = verify_records(_recs(
        S0, A0, {"kind": "integrity", "replica": None,
                 "incarnation": 1, "taint": {}}))
    assert "J008" in _codes(d2)
    d3 = verify_records(_recs(
        S0, A0, {"kind": "integrity", "replica": "r1",
                 "incarnation": 1}))  # missing taint
    assert "J008" in _codes(d3)


def test_j005_composes_with_taint_truncation():
    # after a taint truncation the done-vs-progress audit judges the
    # TRUNCATED accumulation: a done still carrying the tainted suffix
    # is a J005 mismatch (the corrupt tokens were laundered back)
    diags = verify_records(_recs(
        S0, A0, _prog([10, 11, 12]),
        _integrity({0: (1, 3)}),
        {"kind": "assign", "rid": 0, "replica": "r0", "incarnation": 1,
         "gen": 1},
        # survivor "re-decodes" nothing and the done keeps the tainted
        # tokens — accumulated progress is [10], done says [10, 11, 12]
        _fin([10, 11, 12], replica="r0", gen=1),
    ))
    assert "J005" in _codes(diags)


def test_trip_kind_picks_the_taint_window_start(tmp_path):
    """Soundness of the canary vouch (review hardening): a clean
    canary exercises the engine-GLOBAL compute path, so its mark may
    tighten only canary-kind trips (the garble class). A
    fingerprint/trap trip is block-level corruption the canary never
    attended through — its window must open at the ASSIGNMENT base,
    or tokens decoded through a flipped block between the flip and
    its detection would be laundered past the window."""
    from paddle_tpu.analysis.sched_explore import ScriptEngine

    class SlowScript(ScriptEngine):
        # one scripted token per ~20ms: the request must still be
        # MID-FLIGHT when the drill trips it (a bare ScriptEngine
        # finishes before the poll loop can observe progress)
        def step(self):
            time.sleep(0.02)
            return super().step()

    cfg = type("Cfg", (), {"max_len": 64})()
    params = {"pos": np.zeros((64, 4), np.float32)}
    for kind, want_from in (("fingerprint", 0), ("canary", 2)):
        jpath = str(tmp_path / ("trip_%s.jsonl" % kind))
        fleet = ServingFleet(params, cfg,
                             n_replicas=2, journal_path=jpath,
                             heartbeat_timeout_s=120.0,
                             monitor_interval_s=0.01,
                             engine_factory=SlowScript)
        try:
            h = fleet.submit([4, 2], 40, slo=None)
            deadline = time.monotonic() + 30
            while not h.done \
                    and len(fleet._journal.progress_of(h.rid)) < 3:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            assert not h.done, "request outran the drill"
            with fleet._cond:
                a = fleet._journal.assigned_to(h.rid)
                i = int(a[0][1:])  # "rN"
                # a clean canary vouched for the first 2 tokens
                fleet._canary_mark[i][h.rid] = 2
                fleet._integrity_trip_locked(
                    i, fleet._replicas[i],
                    IntegrityError("drill", kind=kind))
            fleet._flush_journal()
            h.result(timeout=60)  # survivor finishes it
        finally:
            fleet.close()
        recs = [json.loads(line) for line in open(jpath)]
        windows = [rec["taint"] for rec in recs
                   if rec["kind"] == "integrity"]
        assert windows and windows[0][str(h.rid)][0] == want_from, (
            kind, windows)
        assert verify_journal(jpath, expect_closed=True) == []


def test_roll_weights_refuses_explicit_golden_fleet_without_new_golden(
        params, cfg, tmp_path):
    # an explicit-golden fleet (the quantized/scripted shape) rolling
    # to new weights without a fresh golden would false-trip every
    # post-rollout canary into an endless quarantine loop — refused
    # with the fleet untouched; passing canary_golden= proceeds
    from paddle_tpu.serving import RolloutAborted

    golden = golden_trace(params, cfg, (1, 2, 3), 4)
    fleet = ServingFleet(params, cfg, n_replicas=1,
                         heartbeat_timeout_s=120.0,
                         canary_interval_s=30.0, canary_golden=golden,
                         engine_kw={"max_slots": 2})
    try:
        with pytest.raises(RolloutAborted, match="canary_golden"):
            fleet.roll_weights(params=params, version=5)
        st = fleet.stats()
        assert st["weights_version"] == 0  # untouched
        assert st["rollout_aborts"] == 1
        out = fleet.roll_weights(params=params, version=5,
                                 canary_golden=golden)
        assert out["version"] == 5
        assert fleet._golden_for(5) == golden
    finally:
        fleet.close()


# ---------------------------------------------------------------------
# explorer scenario (tier-1 smoke; the lint gate explores more)
# ---------------------------------------------------------------------

def test_integrity_trip_scenario_smoke(tmp_path):
    from paddle_tpu.analysis.sched_explore import SCENARIOS, explore

    rep = explore(SCENARIOS["integrity_trip"], str(tmp_path),
                  max_schedules=3)
    assert rep.ok, rep.violation and rep.violation.violations
