"""The bench measurement protocol itself (r3/r4 falsifiability asks +
r4 verdict #9 compile-time budget): pure-python tests of bench._diff_time
— no device, no model, just the timing contract the driver's records
rely on."""

import time

import numpy as np
import pytest

import bench


class FakeRunner(object):
    """run_at(steps) stub with controllable per-step cost + warm cost."""

    def __init__(self, per_step=0.004, first_extra=0.05, overhead=0.0):
        self.calls = []
        self.per_step = per_step
        self.first_extra = first_extra
        self.overhead = overhead  # additive per-call cost (dispatch + sync)

    def __call__(self, steps):
        extra = self.first_extra if steps not in [
            s for s, _ in self.calls
        ] else 0.0
        self.calls.append((steps, extra))
        time.sleep(steps * self.per_step + extra + self.overhead)


def test_diff_time_record_carries_protocol_fields():
    r = FakeRunner()
    dt, info = bench._diff_time(r, 2, 6, return_info=True,
                                scale_steps=False)
    # the per-step estimate lands near the configured cost
    assert 0.5 * r.per_step < dt < 3.0 * r.per_step
    # r4 falsifiability fields
    assert info["steps"] == [2, 6]
    assert set(info["raw_chunk_s"]) == {"2", "6"}
    assert all(
        len(v) >= bench.TIMING_CHUNKS for v in info["raw_chunk_s"].values()
    )
    assert set(info["spread"]) == {"2", "6"}
    assert isinstance(info["stable"], bool)
    # r4 verdict #9: trace+compile budget column — the warm pass is the
    # only one that pays compile, and its extra cost must be visible
    assert set(info["warm_s"]) == {"2", "6"}
    assert info["warm_s"]["2"] >= r.first_extra * 0.5
    # warm includes the first-run extra; steady chunks must not
    assert min(info["raw_chunk_s"]["2"]) < r.first_extra + 2 * 0.004 * 2


def test_diff_time_single_outlier_trimmed_stable(monkeypatch):
    """One gross host stall among >=4 chunks must not flip the
    verdict: the worst chunk is dropped (visibly) for the flag.
    SPREAD_LIMIT is widened so host scheduler jitter on these small
    sleeps cannot register as a second outlier (timing-flake guard)."""
    monkeypatch.setattr(bench, "SPREAD_LIMIT", 0.3)
    r = FakeRunner(per_step=0.02, first_extra=0.01)
    calls = {"n": 0}

    def run_at(s):
        calls["n"] += 1
        if calls["n"] == 5:  # one timed chunk stalls hard (~10x chunk)
            time.sleep(0.4)
        r(s)

    _, info = bench._diff_time(run_at, 2, 6, return_info=True,
                               scale_steps=False)
    assert info["stable"] is True
    assert info["outliers_dropped"]
    s_hit = next(iter(info["outliers_dropped"]))
    assert info["spread"][s_hit] > bench.SPREAD_LIMIT
    assert info["spread_trimmed"][s_hit] <= bench.SPREAD_LIMIT
    # the raw audit trail keeps the stalled chunk
    assert max(info["raw_chunk_s"][s_hit]) > 0.4


def test_diff_time_repeated_outliers_stay_unstable(monkeypatch):
    """Two stalls in one count cannot be trimmed away — the record
    honestly reports stable=false."""
    monkeypatch.setattr(bench, "SPREAD_LIMIT", 0.3)
    r = FakeRunner(per_step=0.02, first_extra=0.01)
    calls = {"n": 0}

    def run_at(s):
        calls["n"] += 1
        if calls["n"] in (5, 11):
            time.sleep(0.4)
        r(s)

    _, info = bench._diff_time(run_at, 2, 6, return_info=True,
                               scale_steps=False)
    assert info["stable"] is False


def test_diff_time_smooth_drift_not_trimmed():
    """Run-to-run drift just past the gate is NOT a stall: with no
    chunk grossly above the median, nothing is trimmed and the record
    stays stable=false."""
    drifts = iter([0.0, 0.01, 0.02, 0.03, 0.04, 0.05] * 4)

    def run_at(s):
        time.sleep(s * 0.05 + next(drifts))

    _, info = bench._diff_time(run_at, 2, 6, return_info=True,
                               scale_steps=False)
    assert info["stable"] is False
    assert "outliers_dropped" not in info


def test_diff_time_drops_sub10ms_probe_from_seeds(monkeypatch):
    """A sub-10 ms probe is the r3 memoized/ack-only signature: it must
    neither drive chunk scaling NOR be merged into raw[] as a steady
    chunk (ADVICE r5 — it deflated dt_min and inflated spread)."""
    monkeypatch.setattr(bench, "MIN_CHUNK_S", 0.10)
    monkeypatch.setattr(bench, "SPREAD_LIMIT", 10.0)  # one round exactly
    r = FakeRunner(per_step=0.001, first_extra=0.01)
    _, info = bench._diff_time(r, 2, 6, return_info=True)
    assert info["chunk_scale"] == 1  # no scaling off the suspect probe
    # raw[] holds ONLY the timed loop's chunks; the ~2 ms probe was
    # dropped instead of seeding the low count
    assert len(info["raw_chunk_s"]["2"]) == bench.TIMING_CHUNKS
    assert len(info["raw_chunk_s"]["6"]) == bench.TIMING_CHUNKS


def test_diff_time_prescale_probe_not_reused_at_final_count(monkeypatch):
    """When the solved scale lands s_lo exactly on base_hi (here (2,6)
    at scale 3 -> s_lo == 6), the pre-scale base_hi probe must NOT be
    merged into raw[s_lo]: it predates the floor verification and could
    consume the single-outlier trim allowance (ADVICE r5). Only the
    post-scale verification probe is reused."""
    monkeypatch.setattr(bench, "MIN_CHUNK_S", 0.12)
    monkeypatch.setattr(bench, "SPREAD_LIMIT", 10.0)  # one round exactly
    r = FakeRunner(per_step=0.02, first_extra=0.01)
    _, info = bench._diff_time(r, 2, 6, return_info=True)
    assert info["chunk_scale"] == 3
    assert info["steps"] == [6, 18]
    # s_lo == 6 == base_hi: TIMING_CHUNKS timed chunks + the ONE
    # post-scale verification probe — the pre-scale probe at 6 is gone
    assert len(info["raw_chunk_s"]["6"]) == bench.TIMING_CHUNKS + 1
    assert len(info["raw_chunk_s"]["18"]) == bench.TIMING_CHUNKS


def test_diff_time_inversion_raises():
    """A pathological runner where more steps are FASTER must be
    rejected, not silently recorded (timing inversion guard)."""

    def weird(steps):
        time.sleep(0.06 if steps == 2 else 0.01)

    with pytest.raises(AssertionError, match="timing inversion"):
        bench._diff_time(weird, 2, 6, return_info=True, scale_steps=False)


def test_diff_time_scales_short_chunks(monkeypatch):
    """r5: a chunk shorter than MIN_CHUNK_S cannot pass the spread gate
    against additive per-call jitter, so the counts are scaled up until
    the low chunk reaches the floor (run_at must accept any count)."""
    monkeypatch.setattr(bench, "MIN_CHUNK_S", 0.10)
    r = FakeRunner(per_step=0.012, first_extra=0.01)
    dt, info = bench._diff_time(r, 2, 6, return_info=True)
    # probes: t(2)~0.024s, t(6)~0.072s -> per_step 0.012, overhead 0
    # -> scale ceil(0.10/0.024) = 5
    scale = info["chunk_scale"]
    assert scale > 1
    assert info["steps"] == [2 * scale, 6 * scale]
    assert set(info["raw_chunk_s"]) == {str(2 * scale), str(6 * scale)}
    # the converged low chunk actually reaches the floor
    assert min(info["raw_chunk_s"][str(2 * scale)]) >= 0.8 * 0.10
    # the estimate still lands near the configured per-step cost
    assert 0.5 * r.per_step < dt < 3.0 * r.per_step
    # the scaled counts were warmed (compile budget stays visible);
    # the original low count's warm is kept for the audit trail
    assert str(2 * scale) in info["warm_s"]
    assert str(6 * scale) in info["warm_s"]


def test_diff_time_rescales_against_call_overhead(monkeypatch):
    """Per-call overhead inflates a naive single-probe scale
    (undershooting the floor by (scale-1)*overhead); the two-point
    solve separates overhead from per-step cost and must land the low
    chunk on the floor anyway."""
    monkeypatch.setattr(bench, "MIN_CHUNK_S", 0.2)
    r = FakeRunner(per_step=0.005, first_extra=0.0, overhead=0.05)
    _, info = bench._diff_time(r, 2, 6, return_info=True)
    scale = info["chunk_scale"]
    # naive ceil(floor/probe) from t(2)=0.06s would pick 4 -> chunk
    # 0.09s; the solve must go further (exact answer: 15)
    assert scale > 4
    assert min(info["raw_chunk_s"][str(2 * scale)]) >= 0.8 * 0.2


def test_diff_time_corrects_stalled_hi_probe(monkeypatch):
    """A stall during the s_hi probe inflates the fitted per-step cost,
    so the solved scale undershoots the floor; the post-scale
    verification probe must catch it and rescale once."""
    monkeypatch.setattr(bench, "MIN_CHUNK_S", 0.2)
    per_s_calls = {}

    def run_at(s):
        per_s_calls[s] = per_s_calls.get(s, 0) + 1
        extra = 0.01 if per_s_calls[s] == 1 else 0.0  # compile on warm
        if s == 6 and per_s_calls[s] == 2:
            extra += 0.3  # the probe call at s_hi stalls
        time.sleep(s * 0.01 + extra)

    _, info = bench._diff_time(run_at, 2, 6, return_info=True)
    scale = info["chunk_scale"]
    # solve off the stalled pair picks ~2; the verified chunk (0.04 s)
    # forces the correction to ceil(2*0.2/0.04) = 10
    assert scale >= 8
    assert info["steps"] == [2 * scale, 6 * scale]
    assert min(info["raw_chunk_s"][str(2 * scale)]) >= 0.8 * 0.2


def test_diff_time_suspect_probe_does_not_scale(monkeypatch):
    """A probe under 10 ms is the r3 memoized/ack-only signature: scaling
    off it would saturate at MAX_CHUNK_SCALE and waste the side budget,
    so the requested counts are kept instead."""
    monkeypatch.setattr(bench, "MIN_CHUNK_S", 1.0)
    # ~2 ms probe: suspect (under 10 ms) yet above the sleep-scheduler
    # noise floor, so the timed chunks still order correctly — with the
    # suspect probe no longer seeding raw[], a 0.1 ms/step runner sat
    # entirely inside scheduler jitter and inverted the differencing
    r = FakeRunner(per_step=0.001, first_extra=0.0)
    _, info = bench._diff_time(r, 2, 6, return_info=True)
    assert info["chunk_scale"] == 1
    assert info["steps"] == [2, 6]


def test_input_pipeline_workload_prefetch_overlap(tmp_path, monkeypatch):
    """ISSUE 3 CI satellite: the `input_pipeline` workload runs green on
    the host backend, is deterministic in WHAT it delivers (checksums
    match between the two runs), and shows the prefetch-on loader-wait
    fraction strictly below prefetch-off on the same fixed-seed trace.
    The decode cost is pinned with the GIL-releasing sleep knob so the
    contrast is about the pipeline, not scheduler jitter."""
    monkeypatch.setenv("BENCH_DATA_DIR", str(tmp_path))
    rec = bench.bench_input_pipeline(
        n_shards=2, chunks_per_shard=3, records_per_chunk=32, batch=16,
        step_s=0.004, decode_sleep_s=0.0003)
    assert rec["prefetch_off"]["records"] == 2 * 3 * 32
    assert rec["prefetch_on"]["records"] == 2 * 3 * 32
    # prefetch must never change the delivered stream
    assert rec["prefetch_on"]["checksum"] == rec["prefetch_off"]["checksum"]
    # the acceptance inequality: overlap strictly cuts the wait share
    assert rec["wait_fraction_on"] < rec["wait_fraction_off"], rec
    assert rec["overlap_speedup"] > 1.0
    # record contract fields the driver's evidence trail relies on
    for k in ("batches_per_sec_on", "batches_per_sec_off", "trace",
              "num_workers", "prefetch_batches"):
        assert k in rec


def test_training_sentinel_workload_contract():
    """ISSUE 10 acceptance: the `training_sentinel` row cannot decay
    into a no-op — on the fixed-seed poisoned run the bench itself
    raises unless >=1 sentinel trip happens, every rollback lands on
    the last KNOWN-GOOD step (the next incarnation resumes exactly
    there), the poison chunk id appears in the quarantine journal
    exactly once (and is the ONLY chunk quarantined — attribution is
    exact on this trace), training completes with a finite committed
    loss curve bit-identical to a clean run that never saw the chunk,
    and, separately, resume with a corrupted LATEST checkpoint
    succeeds with zero manual intervention (bad dir renamed .corrupt,
    the failing CRC named, the walk-back landing one step earlier)."""
    rec = bench.bench_training_sentinel()
    assert rec["sentinel_trips"] >= 1
    assert rec["rollbacks_landed_on_known_good"]
    assert rec["quarantined_chunks"] == [rec["poison_chunk"]]
    assert rec["poison_journaled_once"]
    assert rec["curve_finite"] and np.isfinite(rec["final_loss"])
    assert rec["curve_matches_clean"]
    assert rec["record_stream_matches_clean"]
    assert rec["incarnations"] >= 3  # trip, replay-trip, recovery
    cr = rec["corrupt_resume"]
    assert cr["ok"]
    assert cr["walked_back_to"] < cr["corrupted_step"]
    assert cr["renamed_to"].endswith(".corrupt")
    assert "CRC" in cr["problem"]


def test_training_sentinel_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"training_sentinel", bench_training_sentinel' in src


def test_serving_shared_prefix_workload_contract():
    """ISSUE 4 satellite: the `serving_shared_prefix` row cannot decay
    into a no-op — on the fixed-seed shared-header trace (tiny model,
    host backend) the cache-ON run computes STRICTLY fewer prefill
    tokens than cache-OFF at the same fixed per-token cost (the counted
    tokens, not wall time), the hit rate is positive, and the bench
    itself asserts greedy outputs identical between the two runs. A
    handful of requests are also checked against the sequential
    generate() oracle by the slow-marked companion drill below."""
    rec = bench.bench_serving_shared_prefix(
        n_requests=6, families=2, header_len=8, family_len=4,
        max_slots=2, dim=32, heads=4, layers_n=2, vocab=64, max_len=64,
        chunk_tokens=8, block_tokens=4, cache_tokens=64)
    assert rec["prefill_tokens_computed_on"] < \
        rec["prefill_tokens_computed_off"], rec
    assert rec["prefix_hit_rate"] > 0
    assert rec["prefix_tokens_saved"] > 0
    assert rec["decode_traces_on"] == 1


@pytest.mark.slow  # ~8s of sequential generate() oracles on top of the
# tier-1 contract above (which already pins on==off outputs in-bench)
def test_serving_shared_prefix_outputs_match_generate():
    """ISSUE 4 acceptance on the bench trace itself: requests built
    exactly like the workload's (same seed-0 draw order) decode to
    sequences bit-identical to sequential generate() through the
    prefix-cached chunked engine."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm

    # rebuild the deterministic request stream the bench derives from
    # seed 0 (header, families, arrival draws, then per-request draws)
    cfg = tlm.TransformerConfig(vocab=64, dim=32, heads=4, layers=2,
                                max_len=64)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    header = rng.randint(0, 64, 8).astype(np.int32)
    fam = [rng.randint(0, 64, 4).astype(np.int32) for _ in range(2)]
    rng.exponential(1.0 / 2.0, 6)  # the n_requests=6 arrival draws
    # precede the per-request draws in the bench's stream
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(params, cfg, max_slots=2,
                        prefill_chunk_tokens=8, prefix_cache_tokens=64,
                        prefix_block_tokens=4)
    hs = []
    for _ in range(3):  # first 3 requests of the trace suffice
        f = int(rng.randint(2))
        tail = rng.randint(0, 64, int(rng.randint(4, 13))).astype(np.int32)
        prompt = np.concatenate([header, fam[f], tail])
        n = int(rng.randint(4, 11))
        hs.append((prompt, n, eng.submit(prompt, n, publish_len=12)))
        eng.run()  # sequentially, so request 2+ hits the pool
    assert eng.prefix_cache.stats()["hits"] >= 2
    for prompt, n, h in hs:
        want = np.asarray(
            tlm.generate(params, jnp.asarray(prompt)[None], cfg, n))[0]
        got = np.concatenate([h.prompt, np.asarray(h.tokens, np.int32)])
        np.testing.assert_array_equal(got, want)


def test_serving_fleet_workload_contract():
    """ISSUE 6 satellite: the `serving_fleet` row cannot decay into a
    no-op — on the fixed-seed shared-header trace (tiny model, host
    backend) the kill drill loses ZERO requests and answers none
    twice, exactly one failover happens, the pools actually reuse
    prefixes, and the bench itself raises unless outputs are
    token-identical across the single-replica, fleet+kill, and
    affinity-off runs. (The strict affinity-on > affinity-off reuse
    inequality is pinned by the dedicated no-kill drill in
    test_serving_fleet.py — here the kill erases one replica's pool
    mid-trace, so the cross-run contrast is reported, not asserted.)"""
    rec = bench.bench_serving_fleet(
        n_replicas=2, n_requests=6, families=2, header_len=8,
        family_len=4, max_slots=2, dim=32, heads=4, layers_n=2,
        vocab=64, max_len=64, chunk_tokens=8, block_tokens=4,
        cache_tokens=96)
    assert rec["requests_lost"] == 0, rec
    assert rec["duplicate_completions"] == 0, rec
    assert rec["failovers"] == 1, rec
    assert rec["resubmitted"] >= 0
    assert rec["completed"] == 6 + 2  # paced trace + warm wave
    assert rec["prefix_hit_rate_on"] > 0, rec
    assert rec["prefix_tokens_saved_affinity_on"] > 0, rec
    assert rec["kill_drill"]["replica"] == 0


def test_serving_paged_workload_contract():
    """ISSUE 7 acceptance: the `serving_paged` row cannot decay into a
    no-op — at ONE fixed KV budget on the fixed-seed Poisson trace the
    paged block pool holds STRICTLY more resident slots than the
    [S, max_len]-slab-equivalent engine, the speculative run reports an
    accept-rate (drafts were actually verified), the decode and
    spec-verify steps trace exactly once each, and the bench itself
    raises unless greedy outputs are token-identical across the slab,
    paged, and speculative runs (zero output divergence)."""
    rec = bench.bench_serving_paged(
        n_requests=6, max_slots=6, dim=32, heads=4, layers_n=2,
        vocab=64, max_len=64, block_tokens=4, budget_tokens=128,
        spec_draft_len=4)
    assert rec["slots_resident_paged"] > rec["slots_resident_slab"], rec
    assert rec["slots_resident_slab"] == 128 // 64  # the slab wall
    assert rec["spec_accept_rate"] is not None
    assert 0.0 <= rec["spec_accept_rate"] <= 1.0
    assert rec["spec_windows"] > 0
    assert rec["decode_traces_paged"] == 1
    assert rec["spec_verify_traces"] == 1
    # reservation discipline visible in the row: early-EOS/short tails
    # returned capacity, and the pool never exceeded its budget
    assert rec["peak_kv_blocks_in_use"] <= rec["kv_pool_blocks"]


def test_serving_paged_kernel_workload_contract():
    """ISSUE 13 acceptance: the `serving_paged_kernel` row cannot decay
    into a no-op — on the fixed-seed shared-header trace the fused
    (Pallas table-walk) run performs ZERO `_paged_view` gathers, keeps
    the one-compiled-step discipline (fused decode and spec-verify each
    traced exactly once), and the bench itself hard-raises unless
    greedy outputs are token-identical between the gather and fused
    runs (its divergence gate stays armed under -O)."""
    rec = bench.bench_serving_paged_kernel(
        n_requests=5, max_slots=3, dim=32, heads=4, layers_n=2,
        vocab=64, max_len=64, block_tokens=8, chunk_tokens=16,
        cache_tokens=256, spec_draft_len=4)
    assert rec["paged_view_calls_fused"] == 0, rec
    assert rec["decode_traces_fused"] == 1, rec
    assert rec["spec_verify_traces_fused"] == 1, rec
    assert rec["paged_kernel_fused"] == "fused"
    assert rec["paged_kernel_gather"] == "gather"
    # the reuse surface was actually exercised (aliasing + chunking):
    # a trace that stopped covering it would pass identity vacuously
    assert rec["prefill_traces_fused"] >= 1
    assert rec["tokens_out"] > 0


def test_serving_quant_workload_contract():
    """ISSUE 14 acceptance: the `serving_quant` row cannot decay into
    a no-op — at ONE fixed KV byte budget on the fixed-seed
    shared-header trace, int8 KV holds STRICTLY more resident slots
    than f32 (the bench itself hard-raises otherwise), every
    variant's greedy-prefix agreement vs the f32 run meets its armed
    quality gate (ditto), the pool multiplier reflects int8's ~4x
    blocks per byte, bytes-per-resident-token drops accordingly (with
    the scale side-band's overhead visible, not hidden), and the
    one-compiled-step discipline survives quantization."""
    rec = bench.bench_serving_quant(
        n_requests=6, max_slots=6, dim=32, heads=4, layers_n=2,
        vocab=64, max_len=64, block_tokens=8, chunk_tokens=16,
        cache_tokens=256)
    v = rec["variants"]
    assert v["int8"]["slots_resident"] > v["none"]["slots_resident"], rec
    assert v["int8"]["kv_pool_blocks"] > 3 * v["none"]["kv_pool_blocks"]
    # agreement met its gate for every variant (the bench raises on a
    # miss — these pin the record carries the evidence)
    for name, row in v.items():
        assert row["agreement_vs_f32"] >= row["agreement_gate"], (name, row)
    assert v["none"]["agreement_vs_f32"] == 1.0
    # bytes-per-resident-token: int8 payload is 1/4 f32's, plus the
    # per-block scale overhead (2 bands x layers x heads x 4B / Bt)
    f32_bpt = v["none"]["bytes_per_resident_token"]
    int8_bpt = v["int8"]["bytes_per_resident_token"]
    assert int8_bpt < f32_bpt / 3
    assert int8_bpt > f32_bpt / 4  # the scale side-band is not free
    assert rec["pool_multiplier_int8"] > 3
    assert v["weight_int8"]["weight_quant"] == "int8"
    assert v["weight_int8"]["kv_quant"] == "none"


def test_serving_quant_gate_stays_armed():
    """The quality gate is a hard raise, not a report: a floor no run
    can meet must blow up the bench (guards against the gate decaying
    into a logged number nobody checks)."""
    with pytest.raises(RuntimeError, match="quality gate"):
        bench.bench_serving_quant(
            n_requests=4, max_slots=4, dim=32, heads=4, layers_n=2,
            vocab=64, max_len=64, block_tokens=8, chunk_tokens=16,
            cache_tokens=256, agreement_gate=1.01)


def test_serving_quant_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_quant", bench_serving_quant' in src


def test_kv_bytes_per_token_cost_model():
    """ISSUE 14 satellite: bench_offline's bytes-per-token takes the
    storage dtype into account — int8 cuts the f32 payload 4x plus an
    explicit scale-amortisation term (never free), and the roofline
    record predicts a strictly higher HBM-bound tokens/s for int8
    weights + int8 KV than for the bf16/f32 baseline."""
    import bench_offline as bo

    f32 = bo.kv_bytes_per_token(2, 4, 8, "none", 8, act_itemsize=4)
    i8 = bo.kv_bytes_per_token(2, 4, 8, "int8", 8)
    assert f32 == 2 * 2 * 4 * 8 * 4
    assert i8 == 2 * 2 * 4 * 8 * 1 + 2 * 2 * 4 * 4 / 8.0
    assert f32 / 4 < i8 < f32 / 3
    rec = bo.offline_serving_quant_roofline(layers_n=2, dim=64, heads=4,
                                            vocab=256, S=4, context=64,
                                            block_tokens=8)
    base = rec["w_none_bf16__kv_none"]["pred_tokens_per_sec_hbm_bound"]
    best = rec["w_int8__kv_int8"]["pred_tokens_per_sec_hbm_bound"]
    assert best > base
    assert rec["pred_uplift_int8_over_bf16"] > 1.0


def test_serving_paged_kernel_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_paged_kernel", bench_serving_paged_kernel' in src


def test_serving_slo_workload_contract():
    """ISSUE 8 acceptance: the `serving_slo` row cannot decay into a
    no-op — on the fixed-seed Poisson trace of deadline-carrying
    interactive requests, ZERO requests expire under the gray-slow
    drill (the slowed replica is demoted and its work hedged to
    survivors with token-level resume), resumed requests re-decode
    zero already-emitted tokens (the bench audits the journal: per
    rid, progress deltas concatenate EXACTLY to the done record — a
    re-decoded token would appear twice — and raises otherwise), the
    replica is probed and restored under the SAME incarnation (warm
    pool, no fresh spawn), and the bench itself raises unless outputs
    are token-identical between the healthy and gray runs."""
    rec = bench.bench_serving_slo(n_requests=8)
    assert rec["expired_healthy"] == 0, rec
    assert rec["expired_gray"] == 0, rec
    assert rec["requests_lost"] == 0, rec
    assert rec["demotions_gray"] >= 1, rec
    assert rec["restores_gray"] >= 1, rec
    assert rec["restored_same_incarnation"], rec
    # token-level resume actually ran, and the journal audit (which
    # hard-raises on any re-decoded token) saw the multi-holder rids
    assert rec["resumed_requests"] >= 1, rec
    assert rec["resumed_rids_journal"] >= 1, rec
    assert rec["redecoded_tokens"] == 0, rec
    # the tail bound: gray p99 TTFT within healthy + the slow window
    assert rec["p99_ttft_gray_s"] is not None
    assert rec["p99_ttft_gray_s"] < \
        rec["p99_ttft_healthy_s"] + rec["p99_ttft_excess_bound_s"], rec


def test_serving_elastic_workload_contract():
    """ISSUE 11 acceptance: the `serving_elastic` row cannot decay
    into a no-op — on the fixed-seed Poisson burst of deadline-carrying
    requests, the elastic run must spawn >= 1 replica mid-burst and
    retire >= 1 after it (full scale-up -> scale-down cycle), migrate
    >= 1 request from the prefill tier to a decode tier at first token,
    complete exactly one mid-trace roll_weights onto a CRC-verified
    checkpoint, abort exactly one rollout on the corrupted candidate
    (fleet untouched — the bench hard-raises if any live replica left
    the rolled version), expire and lose NOTHING, and produce outputs
    token-identical to the static tiered fleet (the bench raises on
    any divergence, any duplicated rid, and any J-code — including the
    J009 mixed-version fence — from the journal replay)."""
    rec = bench.bench_serving_elastic(n_requests=8)
    assert rec["expired"] == 0, rec
    assert rec["requests_lost"] == 0, rec
    assert rec["replicas_spawned"] >= 1, rec
    assert rec["replicas_retired"] >= 1, rec
    assert rec["migrations"] >= 1, rec
    assert rec["rollouts_completed"] == 1, rec
    assert rec["rollout_aborts"] == 1, rec
    assert rec["outputs_identical_to_static"], rec
    # the rollout actually moved the fleet: version 1 responses exist
    # alongside pre-rollout version 0 ones, and the fleet ends on 1
    assert rec["weights_version_final"] == 1, rec
    assert 1 in rec["done_versions_seen"], rec
    # migrations rode the journaled resume path (tokens carried over)
    assert rec["resumed_requests"] >= 1, rec


def test_serving_multitenant_workload_contract():
    """ISSUE 12 acceptance: the `serving_multitenant` row cannot
    decay into a no-op — on the fixed-seed 3-tenant Poisson mix with
    one tenant bursting past its quota, the well-behaved
    deadline-class tenants record ZERO deadline misses, the burst is
    shed via TenantQuotaExceeded and never FleetSaturated (and the
    bench checks the journal holds exactly the accepted submits — a
    shed is never journaled), the 3-adapter-through-2-slot pool
    LRU-pages (>= 1 eviction), the zoo batch lane's Executor results
    match the direct run, and every tenant's outputs are
    token-identical to its per-tenant sequential run (all of these
    hard-raise in-bench; the assertions here pin the row's shape)."""
    rec = bench.bench_serving_multitenant(n_requests=6)
    assert rec["deadline_misses_well_behaved"] == 0, rec
    assert rec["requests_lost"] == 0, rec
    assert rec["quota_shed"] == 4, rec
    assert rec["hog_admitted"] == 2, rec
    assert rec["fleet_saturated_shed"] == 0, rec
    assert rec["adapter_evictions"] >= 1, rec
    assert rec["batch_jobs_completed"] == 3, rec
    assert rec["outputs_identical_per_tenant"], rec
    assert rec["zoo_results_match_executor"], rec
    # every tenant shows up in the per-tenant O(1) metrics
    assert set(rec["per_tenant"]) == {"alpha", "beta", "gamma",
                                      "hog", "zoo"}, rec
    assert rec["per_tenant"]["zoo"]["completed"] == 3, rec


def test_serving_integrity_workload_contract():
    """ISSUE 15 acceptance: the `serving_integrity` row cannot decay
    into a no-op — on the fixed-seed shared-header Poisson trace, the
    clean run must trip NOTHING (false-positive bar, with canaries
    actually completing), the garble@ drill must trip exactly once via
    a known-answer CANARY mismatch and the flip@ drill exactly once
    via a block FINGERPRINT mismatch, each quarantining the corrupt
    replica under a fresh incarnation, with outputs token-identical to
    the clean run (zero tainted tokens survive — the taint windows
    re-decoded on the healthy survivor), zero rids lost, and every
    journal green through the DFA --expect-closed including the J010
    taint fence (all of these hard-raise in-bench; the assertions here
    pin the row's shape)."""
    rec = bench.bench_serving_integrity(n_requests=6)
    assert rec["trips_clean"] == 0, rec
    assert rec["canaries_ok_clean"] >= 2, rec
    assert rec["trips_garble"] == 1, rec
    assert rec["trip_kind_garble"] == {"canary": 1}, rec
    assert rec["trips_flip"] == 1, rec
    assert rec["trip_kind_flip"] == {"fingerprint": 1}, rec
    assert rec["fp_mismatches_flip"] >= 1, rec
    assert rec["requests_lost"] == 0, rec
    assert rec["outputs_identical"], rec


def test_serving_kv_handoff_workload_contract():
    """ISSUE 16 acceptance: the `serving_kv_handoff` row cannot decay
    into a no-op — on the fixed-seed shared-header Poisson trace
    against ONE store directory, the cold phase must actually spill
    (>= 1 durable record), the tiered handoff phase must migrate >= 1
    request with tokens_recomputed_at_migration EXACTLY 0 and >= 1
    verified package import (re-prefill demoted to a counted
    fallback), the kill drill must leave the killed replica dead with
    nothing lost, and the warm-restarted fleet must warm >= 1 block
    from the store and serve the first shared-header request with
    strictly fewer prefill tokens than the cold phase's first request
    — all with outputs token-identical across the four phases and
    every journal green through the DFA --expect-closed including the
    J011 handoff fence (all of these hard-raise in-bench; the
    assertions here pin the row's shape)."""
    rec = bench.bench_serving_kv_handoff(n_requests=6)
    assert rec["store_records_after_cold"] >= 1, rec
    assert rec["store_spilled_blocks"] >= 1, rec
    assert rec["migrations_handoff"] >= 1, rec
    assert rec["handoff_packages"] >= 1, rec
    assert rec["handoff_imports"] >= 1, rec
    assert rec["tokens_recomputed_at_migration"] == 0, rec
    assert rec["store_warm_blocks"] >= 1, rec
    assert rec["warm_first_prefill_tokens"] \
        < rec["cold_first_prefill_tokens"], rec
    assert rec["outputs_identical"], rec


@pytest.mark.slow  # ~20s: engine compiles + 2-rate socket sweep +
# kill/disconnect drills; tier-1 keeps the registration pin below and
# the ScriptEngine socket drills in test_frontdoor.py
def test_serving_frontdoor_workload_contract():
    """ISSUE 18 acceptance: the `serving_frontdoor` row cannot decay
    into a no-op — on a fixed-seed 2-tenant open-loop sweep over REAL
    sockets, the wire answer must match the direct fleet answer, the
    sweep must exhibit a measurable capacity knee (goodput flat vs
    offered + typed sheds), the kill drill must fail over >= 1
    replica with zero lost/duplicated rids and zero stream-vs-result
    divergence, the disconnect drill must claw back >= 1 abandoned
    stream as a journaled cancel, and the journal must replay green
    through the DFA --expect-closed including the cancelled terminal
    (all hard-raised in-bench; the assertions here pin the row's
    shape). Shrunk knobs: 2 rates bracketing the knee, short windows
    — the knee is relative, the drills absolute."""
    rec = bench.bench_serving_frontdoor(sweep_duration_s=0.6,
                                        rate_factors=(0.25, 2.5))
    assert rec["knee_rate_rps"] is not None, rec
    assert rec["requests_lost"] == 0, rec
    assert rec["duplicates"] == 0, rec
    assert rec["stream_divergent"] == 0, rec
    assert rec["kill_failovers"] >= 1, rec
    assert rec["cancelled"] >= 1, rec
    assert rec["disconnect_cancels"] >= 1, rec
    assert rec["wire_vs_direct_identical"], rec
    assert len(rec["sweep"]) == 2, rec
    top = rec["sweep"][-1]
    assert sum(top["shed"].values()) >= 1, rec
    assert rec["baseline_shed_alice"] == 0, rec


def test_serving_frontdoor_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_frontdoor", bench_serving_frontdoor' in src


def test_serving_kv_handoff_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_kv_handoff", bench_serving_kv_handoff' in src


def test_serving_integrity_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_integrity", bench_serving_integrity' in src


def test_serving_multitenant_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_multitenant", bench_serving_multitenant' in src


def test_serving_elastic_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_elastic", bench_serving_elastic' in src


def test_serving_slo_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_slo", bench_serving_slo' in src


def test_serving_paged_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_paged", bench_serving_paged' in src


def test_serving_fleet_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_fleet", bench_serving_fleet' in src


def test_serving_shared_prefix_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list
    (the registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"serving_shared_prefix", bench_serving_shared_prefix' in src


def test_input_pipeline_registered_in_bench_main():
    """The workload is wired into bench.main()'s side-workload list (the
    registration is what lands it in the driver's record)."""
    import inspect

    src = inspect.getsource(bench.main)
    assert '"input_pipeline", bench_input_pipeline' in src


def test_diff_time_no_scaling_above_floor(monkeypatch):
    """A chunk already at the floor keeps the requested counts — with a
    probe above the 10 ms suspect threshold, so this pins the floor
    comparison itself, not the suspect guard."""
    monkeypatch.setattr(bench, "MIN_CHUNK_S", 0.015)
    r = FakeRunner(per_step=0.012, first_extra=0.01)  # probe ~24 ms
    _, info = bench._diff_time(r, 2, 6, return_info=True)
    assert info["chunk_scale"] == 1
    assert info["steps"] == [2, 6]
