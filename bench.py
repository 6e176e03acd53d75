"""Benchmark suite: training throughput + MFU on the local chip.

Workloads (BASELINE.md units, reference benchmark/ configs as workload
definitions):

  resnet50  — headline: chip training throughput, img/s vs the 1500
              img/s/chip north star (BASELINE.json); a companion
              `resnet50_input_pipeline` record times the SAME model fed
              end-to-end from the native recordio prefetch queue (uint8
              images, normalised on device); the host->device
              bandwidth it saw is reported beside it as h2d_MBps.
  vgg16     — benchmark/paddle/image/vgg.py, img/s
  alexnet   — benchmark/paddle/image/alexnet.py, img/s vs 334 ms/batch
              bs=128 (benchmark/README.md:37 -> 383 img/s)
  googlenet — benchmark/paddle/image/googlenet.py, img/s vs 1149 ms/batch
              bs=128 (benchmark/README.md:50 -> 111.4 img/s)
  lstm      — benchmark/paddle/rnn/rnn.py (2x LSTM h=512, bs=64, seq 100),
              ms/batch vs 184 ms/batch (benchmark/README.md:119)
  resnet50_infer — serving-side: clone(for_test=True) forward, img/s
              vs the reference's only published inference number
              (217.69 img/s CPU MKL-DNN bs=16,
              IntelOptimizedPaddle.md:87)
  transformer_lm — long-context flagship: decoder-only LM (8x512, T=1024,
              flash attention, bf16), tokens/s + MFU; beyond-reference,
              no 2018 baseline
  transformer_lm_large — 12x1024 (heads=16, T=2048, flash, bf16):
              MXU-shaped matmuls; beyond-reference, no 2018 baseline
  transformer_lm_xl — 16x2048 (heads=16, T=2048, B=2): the
              utilization headline — dim-2048 matmuls run the MXU
              near peak (72.2% MFU measured r5); beyond-reference
  serving_decode — continuous-batching serving engine
              (paddle_tpu/serving): aggregate tok/s + mean slot
              occupancy + compile counts under a fixed-seed Poisson
              arrival trace; beyond-reference, no 2018 baseline
  serving_shared_prefix — prefix-cache acceptance (ISSUE 4): the same
              fixed-seed Poisson trace over K prompt families sharing
              a common header, run with the prefix KV pool off vs on;
              reports prefill-tokens-computed both ways, hit rate, and
              TTFT; greedy outputs must match between runs
  serving_paged — paged-KV + speculative-decoding acceptance (ISSUE
              7): the same fixed-seed Poisson trace at ONE fixed KV
              HBM budget through the [S, max_len]-slab-equivalent
              engine, the paged block pool, and paged + self-drafting
              speculative decoding; reports peak resident slots (paged
              must beat slab at equal budget), speculative
              accept-rate, and tok/s per mode; outputs must be
              token-identical across all three runs
  serving_paged_kernel — fused paged-attention kernel acceptance
              (ISSUE 13): the same fixed-seed shared-header trace with
              paged_kernel="gather" vs "fused" (Pallas table-walk, no
              materialised view) across aliasing/COW/chunking/spec;
              hard-raises on any output divergence or any _paged_view
              gather in the fused run; tokens/s contrast on-chip-only
  serving_fleet — fault-tolerant fleet acceptance (ISSUE 6): the same
              fixed-seed shared-header Poisson trace through a
              single replica, an N=3 fleet with prefix-affinity
              routing + a mid-trace kill drill, and an N=3 fleet with
              affinity off; reports requests lost (must be 0),
              duplicate completions (must be 0), failovers, the
              fleet-wide prefix reuse contrast, and tok/s vs the N×1
              ideal; outputs must be token-identical across all runs
  serving_slo — gray-failure / request-SLO acceptance (ISSUE 8): the
              same fixed-seed Poisson trace of deadline-carrying
              interactive requests through a healthy N-replica fleet
              and through the same fleet with one replica gray-slowed
              (slow@ fault: heartbeating, but every step stalls)
              mid-trace; reports expired requests (must be 0 — the
              gray replica is demoted and its work hedged to survivors
              with token-level resume), resumed requests and tokens
              reused (journal-verified: no emitted token is ever
              re-decoded), demote/probe/restore counts, and p99 TTFT
              healthy vs gray (gray must stay under the slow window —
              the demotion bounded the tail); outputs must be
              token-identical across both runs
  serving_elastic — disaggregated elastic fleet acceptance (ISSUE 11):
              the same fixed-seed Poisson BURST trace of
              deadline-carrying requests through a STATIC tiered fleet
              (prefill/decode disaggregation only) and through the
              ELASTIC fleet (autoscaler on, one mid-trace
              roll_weights to a CRC-verified checkpoint of the same
              weights); pins zero expired requests, zero lost or
              duplicated rids, >=1 scale-up spawn and >=1 scale-down
              retirement, >=1 prefill->decode migration, exactly one
              completed rollout, a corrupted-candidate rollout
              aborting with every replica still serving the old
              version, the journal DFA green including the J009
              version fence (no mixed-version output), and outputs
              token-identical between the static and elastic runs
  serving_multitenant — multi-tenant serving acceptance (ISSUE 12):
              a fixed-seed 3-tenant Poisson mix (two well-behaved
              deadline-class tenants with their own LoRA adapters +
              one adapter tenant driving pool eviction) through one
              fleet, with a fourth tenant BURSTING past its
              token-bucket quota mid-trace and a zoo tenant running
              batched Executor inference through the same scheduler;
              pins zero deadline misses for the well-behaved tenants,
              the burst shed via TenantQuotaExceeded and NEVER
              FleetSaturated (and never journaled), >=1 adapter-pool
              eviction (adapters page like KV), batch results equal
              to the direct Executor run, the journal DFA green with
              the typed tenant side-band, and every tenant's outputs
              token-identical to a per-tenant SEQUENTIAL run — N
              adapters batched over one base model change nothing
  serving_integrity — silent-corruption tolerance acceptance
              (ISSUE 15): the same fixed-seed Poisson shared-header
              trace through (a) a clean fleet with canaries +
              fingerprints armed, (b) the same fleet with one replica
              GARBLED mid-trace (garble@ fault: wrong-but-finite
              tokens — only a known-answer canary mismatch can see
              it), and (c) with one resident KV block FLIPPED
              mid-trace (flip@ fault: caught by the block-fingerprint
              spot-check at aliased re-open); pins zero trips/
              mismatches in the clean run, the corrupt replica
              tripping + quarantining EXACTLY once per drill (fresh
              incarnation via the supervisor backoff), zero lost or
              duplicated rids, outputs token-identical to the clean
              run (zero tainted tokens survive — the taint window
              re-decoded on a healthy survivor), and the journal DFA
              green --expect-closed including the J010 taint fence
              (only tainted tokens ever re-decode)
  training_sentinel — silent-failure tolerance acceptance (ISSUE 10):
              a fixed-seed training job over shards containing one
              poisoned chunk; pins >=1 sentinel trip, rollback landing
              on the last KNOWN-GOOD step, the poison chunk journaled
              to quarantine exactly once, a finite committed loss curve
              bit-identical to a clean run that never saw the chunk,
              and (sub-drill) resume succeeding past a corrupted latest
              checkpoint with zero manual intervention. Pure host work
  input_pipeline — host-side loader overlap (paddle_tpu/data):
              RecordShard shards -> ShardedDataset -> DataLoader on a
              fixed-seed synthetic trace, prefetch OFF (synchronous
              baseline) vs ON (decode threads + bounded queue);
              reports batches/s and the loader-wait fraction. Pure
              host work — fully offline-measurable (ISSUE 3)

Timing: per-step cost is measured by differencing two multi-step
`run_repeated` calls ((T(hi)-T(lo))/(hi-lo)), which cancels the
per-call dispatch and sync cost.

MFU = img_per_sec x 3 x fwd_flops_per_sample / the attached chip's
bf16 peak from DEVICE_PEAKS (backward ~= 2x forward for conv/matmul
nets, so train step ~= 3x fwd). A device kind the table does not list
is an error: no MFU is computed against another chip's peak.

Prints one JSON line per workload; the FINAL line is the headline
ResNet-50 record (driver contract) and carries `mfu` and the full
`workloads` map.

Record field glossary (r4 measurement protocol):
  timing.raw_chunk_s   every raw multi-step chunk wall time, per step
                       count — the full audit trail
  timing.per_step_s_min/median  per-step estimates differencing the
                       per-count minima (noise-robust: a host hiccup
                       only ADDs time) and medians
  timing.spread        (max-min)/min of the raw chunks per step count
  timing.spread_trimmed  same after dropping at most ONE worst chunk
                       per count (only when >=4 chunks were taken, the
                       raw spread failed, AND the max chunk is a gross
                       outlier vs the median — a host stall, not
                       smooth drift; the drop is recorded in
                       outliers_dropped and the raw data stays)
  timing.stable / stable  true iff every trimmed spread <=
                       BENCH_SPREAD_LIMIT (default 10%) — a record
                       with stable=false cannot demonstrate progress
                       or regression
  timing.chunk_scale   >1 when step counts were scaled up so the low
                       chunk reaches BENCH_MIN_CHUNK_S (two-point
                       probe of the warmed counts solves out the
                       additive per-call overhead)
  mfu                  model-FLOPs utilisation (published fwd FLOPs x3)
  xla_flops_util       XLA cost-model FLOPs / peak (counts backward
                       dilated convs, ~1.8x model FLOPs on ResNet)
  roofline             arithmetic intensity vs the chip's ridge
                       (peak flops / HBM bytes/s), the bound verdict
                       (hbm|mxu),
                       the cost-model-implied ceiling img/s, and the
                       achieved fraction of that ceiling
"""

from __future__ import annotations

import glob
import json
import os
import struct
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

BASELINE_IMG_PER_SEC = 1500.0  # ResNet-50 north star (BASELINE.json)
# Published per-chip peaks, keyed by the `device_kind` JAX reports.
# Every utilisation in this file divides by the attached chip's row.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,   # bf16 FLOP/s
        "hbm_bw": 819e9,   # HBM bytes/s
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(device_kind=None):
    """The peaks row for `device_kind` (default: the first attached
    device). A kind the table does not list raises — an MFU against
    another chip's peak is a wrong number, not an estimate."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            "no published peaks for device kind %r (DEVICE_PEAKS lists "
            "%s): add its row, with the source, before reporting a "
            "utilisation on it" % (device_kind, sorted(DEVICE_PEAKS))
        ) from None

# forward FLOPs per sample (2 FLOPs per MAC), standard published counts
FWD_FLOPS = {
    "resnet50": 4.09e9,   # 224x224, bottleneck v1
    "vgg16": 15.47e9,     # 224x224
    "vgg19": 19.63e9,     # 224x224
    "alexnet": 1.43e9,    # 224x224 (0.71 GMAC)
    "googlenet": 3.0e9,   # 224x224 inception v1 (1.5 GMAC)
    "mobilenet": 1.14e9,  # 224x224 v1 1.0x (0.57 GMAC)
}

AMP = os.environ.get("BENCH_AMP", "1") == "1"
IMG_DTYPE = "bfloat16" if AMP else "float32"


def _build_image_workload(fluid, model_fn, batch, class_dim=1000, uint8_input=False):
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        if uint8_input:
            # realistic input pipeline: uint8 images cross the host->device
            # link; normalisation happens on device in the compiled step
            raw = fluid.layers.data(name="image", shape=[3, 224, 224], dtype="uint8")
            image = fluid.layers.scale(
                x=fluid.layers.cast(raw, IMG_DTYPE), scale=1.0 / 255.0
            )
        else:
            image = fluid.layers.data(name="image", shape=[3, 224, 224], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        predict = model_fn(image, class_dim)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        avg_cost = fluid.layers.mean(x=cost)
        opt = fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        opt.minimize(avg_cost)
    main_prog.amp = AMP
    return main_prog, startup, avg_cost


_DEADLINE = None  # monotonic deadline set by main(); guards extra compiles


SPREAD_LIMIT = float(os.environ.get("BENCH_SPREAD_LIMIT", "0.10"))
TIMING_CHUNKS = int(os.environ.get("BENCH_TIMING_CHUNKS", "3"))
# floor on the LOW-count chunk's steady-state wall time: per-call host
# jitter is additive, so a chunk much shorter than this cannot pass the
# spread gate no matter how steady the chip is
MIN_CHUNK_S = float(os.environ.get("BENCH_MIN_CHUNK_S", "1.0"))
# bounds the iterative rescale (runtime/compile guard)
MAX_CHUNK_SCALE = int(os.environ.get("BENCH_MAX_CHUNK_SCALE", "32"))


def _diff_time(run_at, s_lo, s_hi, return_info=False, scale_steps=True):
    """Steady-state per-step seconds by differencing two multi-step calls
    (cancels the per-call dispatch/sync overhead).
    `run_at(steps)` must execute `steps` iterations and block until the
    result is real; with scale_steps=True (default) it must accept ANY
    positive step count, because the counts are scaled up until the low
    chunk runs at least MIN_CHUNK_S (callers whose step count has
    semantic meaning — e.g. KV-cache decode length — pass
    scale_steps=False).

    Measurement protocol (falsifiability requirements from the r3
    verdict): warm both step counts (compile), then time >=3 chunks per
    count; if either count's spread ((max-min)/min) exceeds
    SPREAD_LIMIT, take one more round of chunks. The estimate differs
    the per-count MINIMA (min is the noise-robust statistic against
    host noise that can only ADD time); the median-based estimate, every
    raw chunk timing, the spreads, and a `stable` verdict are all
    reported so the record can be audited and two runs compared."""
    warm_s = {}

    def _warm(s):
        if s not in warm_s:
            t0 = time.time()
            run_at(s)  # compile + warm
            warm_s[s] = time.time() - t0

    _warm(s_lo)
    _warm(s_hi)

    def _probe(s):
        t0 = time.time()
        run_at(s)  # steady-state (already compiled)
        return time.time() - t0

    base_lo, base_hi = s_lo, s_hi
    scale = 1
    seeds = {}  # steady chunks measured while scaling; reused as data
    if scale_steps:
        # two-point solve for the scale: probe BOTH already-warmed
        # counts (zero extra compiles), fit t(n) = overhead + n*per_step
        # — the additive per-call overhead that makes a naive
        # scale = ceil(floor/probe) undershoot is solved for exactly.
        t1 = _probe(base_lo)
        # every run_at blocks on a value readback, so a healthy probe is
        # a full execution. A probe under 10 ms means run_at returned
        # without executing (a dispatch acknowledged but not run, or a
        # repeated call served from memory) — scaling off it would
        # saturate at MAX_CHUNK_SCALE and waste the side budget on
        # every workload, so don't scale then;
        # and the suspect probe is NOT a steady-state chunk, so it must
        # not seed raw[] either (it would deflate dt_min and inflate
        # that count's spread — the stable=false flag still fires from
        # the real chunks if the mode persists).
        if t1 >= 0.01:
            seeds.setdefault(base_lo, []).append(t1)
        if 0.01 <= t1 < MIN_CHUNK_S:
            t2 = _probe(base_hi)
            if t2 >= 0.01:
                seeds.setdefault(base_hi, []).append(t2)
            per_step = (t2 - t1) / (base_hi - base_lo)
            if per_step > 0:
                ovh = max(t1 - base_lo * per_step, 0.0)
                need = (MIN_CHUNK_S - ovh) / (base_lo * per_step)
            else:  # probe noise inverted the pair; fall back to ratio
                need = MIN_CHUNK_S / t1
            scale = int(np.clip(np.ceil(need), 1, MAX_CHUNK_SCALE))
    s_lo, s_hi = base_lo * scale, base_hi * scale
    if scale > 1:
        # the probes above ran at the PRE-scale counts. When the solved
        # scale lands a final count on base_hi (e.g. steps (24,144) at
        # scale 6 -> s_lo == 144), merging them would count a pre-scale
        # probe — possibly carrying exactly the stall the corrective-
        # rescale path below exists to absorb — as a steady chunk at
        # the final count and consume the single-outlier trim
        # allowance. Only probes taken at the FINAL counts are reused.
        seeds = {}
    _warm(s_lo)
    _warm(s_hi)
    if scale > 1:
        # verify the solve landed: a stall in the s_hi probe inflates
        # per_step and undershoots the floor. One corrective rescale
        # off the verified chunk (bounded: exactly one).
        tv = _probe(s_lo)
        if tv < MIN_CHUNK_S * 0.9 and scale < MAX_CHUNK_SCALE:
            scale = int(np.clip(
                np.ceil(scale * MIN_CHUNK_S / max(tv, 1e-3)),
                scale + 1, MAX_CHUNK_SCALE))
            s_lo, s_hi = base_lo * scale, base_hi * scale
            _warm(s_lo)
            _warm(s_hi)
        else:
            seeds.setdefault(s_lo, []).append(tv)
    raw = {s_lo: [], s_hi: []}
    # only probes taken at the FINAL counts survive in `seeds`; they are
    # valid steady-state chunks — count them instead of discarding
    # (saves an execution per workload)
    for s, ts in seeds.items():
        if s in raw:
            raw[s].extend(ts)
    rounds = 0
    while True:
        rounds += 1
        for s in (s_lo, s_hi):
            for _ in range(TIMING_CHUNKS):
                t0 = time.time()
                run_at(s)
                raw[s].append(time.time() - t0)
        spread = {
            s: (max(raw[s]) - min(raw[s])) / min(raw[s]) for s in raw
        }
        if max(spread.values()) <= SPREAD_LIMIT or rounds >= 2:
            break
    # stability verdict: a single gross host stall (one chunk at
    # several times its peers) should not flip the flag when the
    # remaining chunks agree — drop at most ONE worst chunk per count,
    # visibly: the full raw data stays in the record and trimmed counts
    # are reported. Guarded so smooth run-to-run drift just past the
    # gate is NOT relabeled stable: the drop needs >=4 chunks AND the
    # max to be a genuine outlier (3x the limit above the median; 12%
    # steady drift is not). The per-step ESTIMATE never used the
    # outlier anyway (min/median differencing).
    spread_trimmed, outliers_dropped = {}, {}
    for s in raw:
        if (
            spread[s] > SPREAD_LIMIT
            and len(raw[s]) >= 4
            and max(raw[s])
            > float(np.median(raw[s])) * (1 + 3 * SPREAD_LIMIT)
        ):
            kept = sorted(raw[s])[:-1]
            spread_trimmed[s] = (max(kept) - min(kept)) / min(kept)
            outliers_dropped[s] = 1
        else:
            spread_trimmed[s] = spread[s]
    dt_min = (min(raw[s_hi]) - min(raw[s_lo])) / (s_hi - s_lo)
    dt_med = float(
        (np.median(raw[s_hi]) - np.median(raw[s_lo])) / (s_hi - s_lo)
    )
    # a hiccup in every lo-count chunk can still invert min-differencing;
    # the median estimate is the fallback before declaring the data bad
    dt = dt_min if dt_min > 0 else dt_med
    assert dt > 0, "timing inversion: %r" % raw
    info = {
        "steps": [s_lo, s_hi],
        # trace+compile+first-execution per signature (each step count
        # jits its own scan): the compile-time budget column (r4 verdict
        # #9 — the reference tracked per-step op-creation overhead,
        # executor.cc:119; ours moved to compile time)
        "warm_s": {str(s): round(warm_s[s], 2) for s in warm_s},
        "raw_chunk_s": {
            str(s): [round(t, 4) for t in raw[s]] for s in raw
        },
        "per_step_s_min": round(dt_min, 6),
        "per_step_s_median": round(dt_med, 6),
        "spread": {str(s): round(spread[s], 4) for s in raw},
        "spread_trimmed": {
            str(s): round(spread_trimmed[s], 4) for s in raw
        },
        "stable": bool(max(spread_trimmed.values()) <= SPREAD_LIMIT),
        # >1 when the requested counts were scaled to reach MIN_CHUNK_S;
        # warm_s then also carries the requested (pre-scale) counts'
        # warms, whose steady probes fed the solve
        "chunk_scale": scale,
    }
    if outliers_dropped:
        info["outliers_dropped"] = {
            str(s): n for s, n in outliers_dropped.items()
        }
    return (dt, info) if return_info else dt


def _jit_per_count(build, consume):
    """run_at factory for the scale_steps contract: jit `build(n)` on
    demand per step count (any count — chunk scaling picks new ones)
    and pass the result to `consume` (which must block on a readback)."""
    fs = {}

    def run_at(n):
        if n not in fs:
            fs[n] = build(n)
        consume(fs[n])

    return run_at


def _per_step_seconds(exe, prog, feed, fetch, s_lo, s_hi):
    def run_at(s):
        out = exe.run_repeated(prog, feed=feed, fetch_list=[fetch], steps=s)
        v = np.ravel(out[0])[-1]
        assert np.isfinite(float(v)), "non-finite loss"

    return _diff_time(run_at, s_lo, s_hi, return_info=True)


def _xla_step_cost(prog, cost, feed):
    """XLA's own cost model for the compiled train step: flops + bytes
    accessed. The model-FLOPs MFU we report is conservative — XLA counts
    ~1.8x more flops for ResNet-50 (backward convs via dilated convs are
    tallied over the dilated windows) — so the record carries both.
    Costs one extra XLA compile (lower().cost_analysis() without compile
    returns None on this backend), so callers deadline-guard it."""
    import jax

    from paddle_tpu.fluid.core.lowering import build_step_fn
    from paddle_tpu.fluid.executor import global_scope

    scope = global_scope()
    persist_names = sorted(
        v.name for v in prog.list_vars() if v.persistable)
    persist_in = {n: scope.get(n) for n in persist_names if n in scope}
    fn, _ = build_step_fn(
        prog, feed_names=list(feed), fetch_names=[cost.name],
        persist_names=persist_names, persist_in=list(persist_in))
    ca = (
        jax.jit(fn)
        .lower(persist_in, feed, jax.random.PRNGKey(0))
        .compile()
        .cost_analysis()
    )
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def bench_image(name, model_fn, batch, steps=(12, 72), baseline_ips=None,
                xla_cost=False, remat=False):
    import jax

    import paddle_tpu.fluid as fluid

    prog, startup, cost = _build_image_workload(fluid, model_fn, batch)
    if remat:
        fluid.memory_optimize(prog)  # forward-region rematerialization
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {
        "image": jax.device_put(rng.rand(batch, 3, 224, 224).astype(np.float32)),
        "label": jax.device_put(rng.randint(0, 1000, (batch, 1)).astype(np.int32)),
    }
    dt, timing = _per_step_seconds(exe, prog, feed, cost, *steps)
    img_per_sec = batch / dt
    peaks = device_peaks()
    peak_flops, hbm_bw = peaks["flops"], peaks["hbm_bw"]
    rec = {
        "img_per_sec": round(img_per_sec, 2),
        "ms_per_batch": round(dt * 1e3, 2),
        "batch": batch,
        "mfu": round(img_per_sec * 3 * FWD_FLOPS[name] / peak_flops, 4),
        "timing": timing,
    }
    if (
        xla_cost
        and os.environ.get("BENCH_XLA_COST", "1") == "1"
        # the extra compile must not push a near-budget run into the
        # watchdog: skip when under 5 minutes remain
        and (_DEADLINE is None or _DEADLINE - time.monotonic() > 300)
    ):
        try:
            flops, hbm_bytes = _xla_step_cost(prog, cost, feed)
            rec["xla_flops_util"] = round(flops / dt / peak_flops, 4)
            rec["hbm_GBps"] = round(hbm_bytes / dt / 1e9, 1)
            # roofline verdict (r3 ask): where does this step sit
            # relative to the chip's machine balance, and how much of
            # the model-implied ceiling is achieved? The ridge point is
            # peak flops / HBM bytes/s; a step below it is
            # bandwidth-bound and its ceiling is bytes/BW.
            if flops > 0 and hbm_bytes > 0:
                ai = flops / hbm_bytes
                t_roof = max(flops / peak_flops, hbm_bytes / hbm_bw)
                rec["roofline"] = {
                    "ai_flops_per_byte": round(ai, 1),
                    "ridge_flops_per_byte": round(peak_flops / hbm_bw, 1),
                    "bound": "hbm" if ai < peak_flops / hbm_bw else "mxu",
                    "roofline_ms": round(t_roof * 1e3, 3),
                    "roofline_img_per_sec": round(batch / t_roof, 1),
                    "achieved_frac_of_roofline": round(t_roof / dt, 4),
                }
        except Exception as e:  # cost model is informational only
            rec["xla_cost_error"] = "%s: %s" % (type(e).__name__, e)
    exe.close()
    if baseline_ips:
        rec["vs_baseline"] = round(img_per_sec / baseline_ips, 4)
    return rec


# ---------------------------------------------------------------------------
# recordio-fed ResNet-50 (headline)
# ---------------------------------------------------------------------------


def _ensure_recordio(path, n_samples, rng):
    """A record per sample: [label u16][raw uint8 3*224*224] — the data
    plane the reference's Go master dispatches (RecordIO chunks)."""
    from paddle_tpu import native

    if os.path.exists(path):
        return
    w = native.RecordWriter(path + ".tmp")
    img_bytes = 3 * 224 * 224
    for _ in range(n_samples):
        label = int(rng.randint(0, 1000))
        img = rng.randint(0, 256, img_bytes, dtype=np.uint8)
        w.write(struct.pack("<H", label) + img.tobytes())
    w.close()
    os.replace(path + ".tmp", path)


def _build_image_infer_program(fluid, model_fn, class_dim=1000):
    """The serving-side program: f32 vars (declaring bf16 vars would
    create bf16 parameters — a different model than the f32 one
    save_inference_model exports; the amp lowering only engages on the
    autodiff path, so this forward runs f32 — conservative, and
    precision-matched to the f32 MKL-DNN baselines), clone(for_test)
    so batch-norm uses moving statistics. Shared with bench_offline so
    the AOT fingerprint always matches the program benched on-chip."""
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        image = fluid.layers.data(
            name="image", shape=[3, 224, 224], dtype="float32")
        pred = model_fn(image, class_dim)
    return main_prog.clone(for_test=True), startup, pred


def bench_image_infer(name, model_fn, baseline_ips, batch=None,
                      steps=None):
    """Image-model inference throughput (img/s): the serving-side rows,
    run through clone(for_test=True) so batch-norm uses the moving
    statistics (the same program save_inference_model would export).
    Reference baselines: the MKL-DNN bs=16 inference table on a 2S Xeon
    Gold 6148 (/root/reference/benchmark/IntelOptimizedPaddle.md:77-107)
    — the only published inference numbers in the reference tree."""
    import jax

    import paddle_tpu.fluid as fluid

    # bs=16 matches the reference baselines; overridable for CPU smokes
    batch = batch or int(os.environ.get("BENCH_INFER_BATCH", "16"))
    steps = steps or tuple(
        int(s)
        for s in os.environ.get("BENCH_INFER_STEPS", "24,144").split(","))
    test_prog, startup, pred = _build_image_infer_program(fluid, model_fn)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {
        "image": jax.device_put(
            rng.rand(batch, 3, 224, 224).astype(np.float32)),
    }
    dt, timing = _per_step_seconds(exe, test_prog, feed, pred, *steps)
    exe.close()
    img_per_sec = batch / dt
    return {
        "img_per_sec": round(img_per_sec, 2),
        "ms_per_batch": round(dt * 1e3, 2),
        "batch": batch,
        "mfu": round(img_per_sec * FWD_FLOPS[name]
                     / device_peaks()["flops"], 4),
        "vs_baseline": round(img_per_sec / baseline_ips, 4),
        "timing": timing,
    }


def bench_resnet50_recordio(batch, chunk_steps, n_chunks):
    """Timed loop fed from the native recordio prefetch queue: each chunk
    of `chunk_steps` batches is decoded on the host while the previous
    chunk trains on device (async dispatch overlaps transfer+compute)."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu import native
    from paddle_tpu.models.resnet import resnet_imagenet

    prog, startup, cost = _build_image_workload(
        fluid,
        lambda img, cd: resnet_imagenet(img, class_dim=cd, depth=50),
        batch,
        uint8_input=True,
    )
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)

    path = os.environ.get("BENCH_RECORDIO", "/tmp/bench_imagenet.rio")
    samples_per_chunk = batch * chunk_steps
    rng = np.random.RandomState(7)
    _ensure_recordio(path, samples_per_chunk * 4, rng)  # cycled reader

    img_bytes = 3 * 224 * 224

    def chunks():
        """Endless chunk stream off the native prefetch queue. Fresh
        buffers per chunk: the consumer may still be uploading the
        previous one (AsyncDeviceFeeder double-buffering below)."""
        imgs = np.empty((chunk_steps, batch, 3, 224, 224), np.uint8)
        lbls = np.empty((chunk_steps, batch, 1), np.int64)
        i = 0
        while True:
            reader = native.PrefetchReader([path], capacity=256)
            for rec in reader:
                s, b = divmod(i, batch)
                lbls[s, b, 0] = struct.unpack("<H", rec[:2])[0]
                imgs[s, b] = np.frombuffer(
                    rec[2 : 2 + img_bytes], np.uint8
                ).reshape(3, 224, 224)
                i += 1
                if i == samples_per_chunk:
                    yield imgs, lbls
                    imgs = np.empty_like(imgs)
                    lbls = np.empty_like(lbls)
                    i = 0

    stream = chunks()
    # compile + warm with the first chunk
    imgs, lbls = next(stream)
    out = exe.run_repeated(
        prog, feed={"image": imgs, "label": lbls}, fetch_list=[cost],
        steps=chunk_steps, scan_feeds=True,
    )
    assert np.isfinite(np.ravel(out[0])[-1])

    # sustained host->device bandwidth, reported beside the pipeline's
    # throughput, which it can bound
    jax.device_put(np.zeros(1024, np.uint8)).block_until_ready()  # warm link
    t0 = time.time()
    probe = jax.device_put(imgs)
    probe.block_until_ready()
    h2d_mbps = imgs.nbytes / 1e6 / (time.time() - t0)
    del probe

    # double-buffered: a background thread decodes + uploads chunk k+1
    # while the device trains on chunk k (fluid.AsyncDeviceFeeder —
    # reference DataProvider.h:249 DoubleBuffer)
    from paddle_tpu.fluid.data_feeder import AsyncDeviceFeeder

    def feed_iter():
        for _ in range(n_chunks):
            imgs_c, lbls_c = next(stream)
            yield {"image": imgs_c, "label": lbls_c}

    t0 = time.time()
    outs = None
    feeder = AsyncDeviceFeeder(feed_iter(), capacity=2)
    try:
        for feed in feeder:
            outs = exe.run_repeated(
                prog, feed=feed, fetch_list=[cost],
                steps=chunk_steps, scan_feeds=True, return_numpy=False,
            )
    finally:
        # a raise mid-loop must not leave the producer pinning device
        # buffers for the rest of the bench process
        feeder.close()
    final_loss = float(np.ravel(np.asarray(outs[0]))[-1])  # full sync
    dt = time.time() - t0
    exe.close()
    assert np.isfinite(final_loss)

    img_per_sec = batch * chunk_steps * n_chunks / dt
    return {
        "img_per_sec": round(img_per_sec, 2),
        "ms_per_batch": round(dt / (chunk_steps * n_chunks) * 1e3, 2),
        "batch": batch,
        "mfu": round(img_per_sec * 3 * FWD_FLOPS["resnet50"]
                     / device_peaks()["flops"], 4),
        "input": "recordio-uint8",
        "h2d_MBps": round(h2d_mbps, 1),
        "note": "end-to-end including host->device transfer "
                "(h2d_MBps above)",
    }


# ---------------------------------------------------------------------------
# LSTM (benchmark/paddle/rnn/rnn.py: 2x LSTM h=512, bs=64, seq 100)
# ---------------------------------------------------------------------------


def bench_profiler_reconciliation(batch=32):
    """r4 verdict #4: on-chip, reconcile the compiled profiler's
    traffic-modeled per-op attribution against MEASURED jax.profiler
    instruction times (reference measured per-op with CUDA events,
    platform/profiler.cc:198). Records both columns for the top ops
    and the top-5 disagreement — <=0.20 is the verdict's pass bar."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import profiler
    from paddle_tpu.models.resnet import resnet_imagenet

    prog, startup, cost = _build_image_workload(
        fluid, lambda i, c: resnet_imagenet(i, class_dim=c, depth=50),
        batch,
    )
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {
        "image": jax.device_put(
            rng.rand(batch, 3, 224, 224).astype(np.float32)),
        "label": jax.device_put(
            rng.randint(0, 1000, (batch, 1)).astype(np.int32)),
    }
    table, meta = profiler.trace_profile(exe, prog, feed, [cost], runs=3)
    exe.close()
    return {
        "backend": meta["backend"],
        "measured_total_ms": meta["measured_total_ms"],
        "unmatched_ms": meta["unmatched_ms"],
        "top5_max_disagreement": meta["top5_max_disagreement"],
        "reconciled": meta["top5_max_disagreement"] <= 0.20,
        "top_rows": table[:8],
    }


def bench_lstm(batch=64, hidden=512, emb=128, seqlen=100, vocab=30000,
               layers_n=2, steps=(8, 48)):
    import jax

    import paddle_tpu.fluid as fluid

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        words = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                  lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        x = fluid.layers.embedding(input=words, size=[vocab, emb])
        for _ in range(layers_n):
            proj = fluid.layers.fc(input=x, size=hidden * 4)
            x, _ = fluid.layers.dynamic_lstm(input=proj, size=hidden * 4)
        last = fluid.layers.sequence_last_step(input=x)
        predict = fluid.layers.fc(input=last, size=2, act="softmax")
        cost = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=predict, label=label)
        )
        fluid.optimizer.Adam(learning_rate=2e-3).minimize(cost)
    main_prog.amp = AMP

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, vocab, (batch * seqlen, 1)).astype(np.int64)
    offsets = np.arange(0, batch * seqlen + 1, seqlen, dtype=np.int32)
    feed = {
        "words": (tokens, [offsets]),
        "label": rng.randint(0, 2, (batch, 1)).astype(np.int64),
    }
    dt, timing = _per_step_seconds(exe, main_prog, feed, cost, *steps)
    exe.close()

    # fwd FLOPs/batch: per LSTM layer, input proj (E or H -> 4H) + the
    # recurrent GEMM (H -> 4H) over T*B tokens, 2 FLOPs/MAC
    toks = batch * seqlen
    f = 0.0
    in_dim = emb
    for _ in range(layers_n):
        f += 2.0 * toks * (in_dim * 4 * hidden + hidden * 4 * hidden)
        in_dim = hidden
    ms = dt * 1e3
    return {
        "ms_per_batch": round(ms, 2),
        "batch": batch,
        "hidden": hidden,
        "seq_len": seqlen,
        "mfu": round((f * 3 / dt) / device_peaks()["flops"], 4),
        "vs_baseline": round(184.0 / ms, 4),  # >1 = faster than reference
        "timing": timing,
    }


def bench_sparse_embedding(vocab=1_000_000, dim=64, batch=4096, fields=8,
                           steps=(8, 40)):
    """CTR-style sparse-embedding training step (SelectedRows path, r4):
    `fields` id lookups per example into a [1M, dim] table, sum-pooled
    into a logistic head, SGD. The sparse step's gradient work scales
    with touched rows (batch*fields), not vocab; the dense run of the
    SAME model is timed for the on-chip comparison. Reference workload
    family: sparse remote updaters + SelectedRows CTR path
    (RemoteParameterUpdater.h:265, operators/sgd_op.cc sparse branch)."""
    import jax

    import paddle_tpu.fluid as fluid

    def build(is_sparse):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ids = fluid.layers.data(name="ids", shape=[fields],
                                    dtype="int64")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            emb = fluid.layers.embedding(
                input=ids, size=[vocab, dim], is_sparse=is_sparse,
            )
            pooled = fluid.layers.reduce_sum(emb, dim=1)
            pred = fluid.layers.fc(input=pooled, size=1, act=None)
            cost = fluid.layers.mean(
                x=fluid.layers.sigmoid_cross_entropy_with_logits(
                    x=pred, label=y
                )
            )
            fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
        return main, startup, cost

    rng = np.random.RandomState(0)
    feed = {
        "ids": rng.randint(0, vocab, (batch, fields)).astype(np.int64),
        "y": (rng.rand(batch, 1) > 0.5).astype(np.float32),
    }

    out = {}
    for is_sparse in (True, False):
        main, startup, cost = build(is_sparse)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        dt, timing = _per_step_seconds(exe, main, feed, cost, *steps)
        exe.close()
        key = "sparse" if is_sparse else "dense"
        out["ms_per_step_" + key] = round(dt * 1e3, 3)
        if is_sparse:
            out["timing"] = timing
            out["examples_per_sec"] = round(batch / dt, 1)
            out["touched_rows_per_sec"] = round(batch * fields / dt, 1)
    out.update(vocab=vocab, dim=dim, batch=batch, fields=fields)
    out["sparse_speedup"] = round(
        out["ms_per_step_dense"] / out["ms_per_step_sparse"], 3
    )
    return out


def bench_transformer_lm(B=8, T=1024, dim=512, heads=8, layers_n=8,
                         vocab=32000, steps=(4, 24)):
    """Decoder-only transformer LM training throughput (tokens/s + MFU):
    the long-context flagship (models/transformer.py) with the pallas
    flash-attention kernel, bf16 params, steps inside one lax.scan.
    Beyond-reference capability — no 2018 baseline exists, reported for
    the record."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.models import transformer as tlm

    impl = "flash" if jax.default_backend() != "cpu" else "xla"
    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=T,
                                dtype=jnp.bfloat16)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    step = tlm.make_train_step(cfg, lr=1e-3, attn_impl=impl)

    def multi(p, toks, n):
        def body(c, _):
            c, l = step(c, toks)
            return c, l

        return lax.scan(body, p, None, length=n)

    rng = np.random.RandomState(0)
    toks = jax.device_put(
        rng.randint(0, vocab, (B, T + 1)).astype(np.int32))

    def _check(f):
        _, losses = f(params, toks)
        assert np.isfinite(float(np.ravel(np.asarray(losses))[-1]))

    run_at = _jit_per_count(
        lambda n: jax.jit(lambda p, t: multi(p, t, n)), _check)

    dt, timing = _diff_time(run_at, *steps, return_info=True)

    # FLOPs: matmul params (tied head counted once at the logits matmul)
    p_mat = vocab * dim + layers_n * 12 * dim * dim
    fwd = 2.0 * B * T * p_mat + layers_n * B * 2.0 * T * T * dim  # causal
    tok_per_sec = B * T / dt
    return {
        "tokens_per_sec": round(tok_per_sec, 1),
        "ms_per_step": round(dt * 1e3, 2),
        "batch": B,
        "seq_len": T,
        "attn_impl": impl,
        "mfu": round(3.0 * fwd / dt / device_peaks()["flops"], 4),
        "timing": timing,
    }


def bench_lm_decode(B=8, T0=512, new_tokens=(64, 192), dim=512, heads=8,
                    layers_n=8, vocab=32000):
    """Cached autoregressive decode throughput (tokens/s/chip): prefill
    once, then KV-cache decode steps inside one lax.scan
    (models/transformer.py generate). The serving-side companion to the
    training record; beyond-reference capability, no 2018 baseline."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=T0 + max(new_tokens),
                                dtype=jnp.bfloat16)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompt = jax.device_put(
        rng.randint(0, vocab, (B, T0)).astype(np.int32))

    gens = {
        n: jax.jit(lambda p, pr, n=n: tlm.generate(p, pr, cfg, n))
        for n in new_tokens
    }

    def run_at(n):
        out = gens[n](params, prompt)
        assert int(np.asarray(out[0, -1])) >= 0

    # seconds per generated token; the step count IS the decode length
    # (bounded by cfg.max_len), so chunk scaling must not touch it
    dt, timing = _diff_time(
        run_at, *new_tokens, return_info=True, scale_steps=False)
    return {
        "decode_tokens_per_sec": round(B / dt, 1),
        "ms_per_token": round(dt * 1e3 / B, 3),
        "batch": B,
        "prompt_len": T0,
        "timing": timing,
    }


def bench_serving_decode(max_slots=None, n_requests=None):
    """Continuous-batching serving engine (paddle_tpu/serving) under a
    synthetic Poisson arrival trace: aggregate decode tokens/s + mean
    slot occupancy + compile counts. The trace is FIXED-SEED and
    measured in engine steps (arrivals are injected by step index, not
    wall clock), so the workload — prompts, budgets, admission order,
    greedy outputs — is fully deterministic: the
    occupancy/compile-count columns are meaningful offline (CPU), the
    tokens/s column only on-chip. Serving counterpart of lm_decode,
    which measures ONE request's decode; this measures many concurrent
    requests sharing one compiled step (ISSUE 2)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingEngine

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: exercises the full engine, seconds not minutes
        dim, heads, layers_n, vocab, max_len = 128, 4, 2, 512, 128
        max_slots = max_slots or 4
        n_requests = n_requests or 12
        p_lo, p_hi, n_lo, n_hi, rate = 4, 48, 4, 16, 2.0
        dtype = jnp.float32
    else:
        dim, heads, layers_n, vocab, max_len = 512, 8, 8, 32000, 1024
        max_slots = max_slots or 16
        n_requests = n_requests or 64
        p_lo, p_hi, n_lo, n_hi, rate = 64, 512, 32, 128, 1.0
        dtype = jnp.bfloat16

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = [
        (
            rng.randint(0, vocab,
                        rng.randint(p_lo, p_hi + 1)).astype(np.int32),
            int(rng.randint(n_lo, n_hi + 1)),
        )
        for _ in range(n_requests)
    ]

    eng = ServingEngine(params, cfg, max_slots=max_slots)
    t0 = time.time()
    i = step = 0
    while i < n_requests or eng.live_slots or eng.queue_depth \
            or eng.prefilling_slots:
        while i < n_requests and arrive_at[i] <= step:
            p, n = reqs[i]
            eng.submit(p, n)
            i += 1
        if not eng.step() and i < n_requests:
            step = max(step + 1, int(arrive_at[i]))  # idle gap: jump
            continue
        step += 1
    wall = time.time() - t0
    rep = eng.metrics.report()
    compile_total = int(sum(eng.metrics.trace_counts.values()))
    return {
        # wall includes the O(#buckets)+1 compiles
        "tokens_per_sec": round(rep["tokens_out"] / wall, 1),
        "tokens_out": rep["tokens_out"],
        "decode_steps": rep["decode_steps"],
        "mean_occupancy": rep["mean_occupancy"],
        "mean_queue_wait_s": rep["mean_queue_wait_s"],
        "mean_ttft_s": rep["mean_ttft_s"],
        "prefill_traces": rep["prefill_traces"],
        "decode_traces": rep["decode_traces"],
        "compile_total": compile_total,
        "max_slots": max_slots,
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0)" % rate,
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_shared_prefix(n_requests=None, families=None,
                                header_len=None, family_len=None,
                                max_slots=None, dim=None, heads=None,
                                layers_n=None, vocab=None, max_len=None,
                                chunk_tokens=None, block_tokens=None,
                                cache_tokens=None):
    """Prefix-cache acceptance trace (ISSUE 4): fixed-seed Poisson
    arrivals over K prompt families sharing a common header (system-
    prompt/few-shot shape — the workload RadixAttention exists for).
    The SAME deterministic trace runs twice through the serving engine —
    prefix cache OFF vs ON — and the row reports the offline-meaningful
    columns: prefill-tokens-computed (the work the cache deletes),
    prefix-hit rate, evictions, and mean TTFT both ways. Greedy outputs
    must be token-identical between the two runs (asserted in-bench:
    reuse must never change what a request decodes to); tokens/s is
    only meaningful on-chip, like the serving_decode row."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingEngine

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: exercises both engine paths, seconds not minutes
        dim, heads, layers_n = dim or 128, heads or 4, layers_n or 2
        vocab, max_len = vocab or 512, max_len or 256
        n_requests, families = n_requests or 12, families or 3
        header_len, family_len = header_len or 32, family_len or 16
        max_slots = max_slots or 4
        t_lo, t_hi, n_lo, n_hi, rate = 4, 12, 4, 10, 2.0
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests, families = n_requests or 64, families or 4
        header_len, family_len = header_len or 256, family_len or 64
        max_slots = max_slots or 16
        t_lo, t_hi, n_lo, n_hi, rate = 16, 64, 32, 128, 1.0
        dtype = jnp.bfloat16
    chunk_tokens = chunk_tokens or max(16, header_len // 2)
    block_tokens = block_tokens or 16
    cache_tokens = cache_tokens or 8 * (header_len + family_len)

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    header = rng.randint(0, vocab, header_len).astype(np.int32)
    fam = [rng.randint(0, vocab, family_len).astype(np.int32)
           for _ in range(families)]
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = []
    for _ in range(n_requests):
        f = int(rng.randint(families))
        tail = rng.randint(0, vocab,
                           int(rng.randint(t_lo, t_hi + 1))).astype(np.int32)
        prompt = np.concatenate([header, fam[f], tail])
        reqs.append((prompt, int(rng.randint(n_lo, n_hi + 1)),
                     header_len + family_len))

    def run_once(pool_tokens):
        eng = ServingEngine(
            params, cfg, max_slots=max_slots,
            prefill_chunk_tokens=chunk_tokens,
            prefix_cache_tokens=pool_tokens,
            prefix_block_tokens=block_tokens)
        hs = []
        i = step = 0
        while i < n_requests or eng.live_slots or eng.queue_depth \
                or eng.prefilling_slots:
            while i < n_requests and arrive_at[i] <= step:
                p, n, pub = reqs[i]
                # publish-boundary tag: only the shared header+family
                # prefix enters the pool, never the unique tails
                hs.append(eng.submit(p, n, publish_len=pub))
                i += 1
            if not eng.step() and i < n_requests:
                step = max(step + 1, int(arrive_at[i]))  # idle gap: jump
                continue
            step += 1
        return eng, [list(h.tokens) for h in hs]

    eng_off, out_off = run_once(None)
    eng_on, out_on = run_once(cache_tokens)
    # reuse must never change what any request decodes to — a hard
    # raise, not a bare assert: the acceptance gate must survive -O
    if out_on != out_off:
        raise RuntimeError("prefix cache changed greedy outputs")
    rep_off, rep_on = eng_off.metrics.report(), eng_on.metrics.report()
    pc = eng_on.prefix_cache.stats()
    return {
        "prefill_tokens_computed_off": rep_off["prefill_tokens_computed"],
        "prefill_tokens_computed_on": rep_on["prefill_tokens_computed"],
        "prefill_tokens_saved_frac": round(
            1.0 - rep_on["prefill_tokens_computed"]
            / max(rep_off["prefill_tokens_computed"], 1), 4),
        "prefix_hit_rate": pc["hit_rate"],
        "prefix_tokens_saved": pc["tokens_saved"],
        "prefix_evictions": pc["evictions"],
        "mean_ttft_s_off": rep_off["mean_ttft_s"],
        "mean_ttft_s_on": rep_on["mean_ttft_s"],
        "decode_steps_off": rep_off["decode_steps"],
        "decode_steps_on": rep_on["decode_steps"],
        "prefill_traces_on": rep_on["prefill_traces"],
        "decode_traces_on": rep_on["decode_traces"],
        "tokens_out": rep_on["tokens_out"],
        "n_requests": n_requests,
        "families": families,
        "arrival": "poisson(rate=%g/step, seed=0)" % rate,
        "knobs": {"prefill_chunk_tokens": chunk_tokens,
                  "prefix_block_tokens": block_tokens,
                  "prefix_cache_tokens": cache_tokens,
                  "publish_len": header_len + family_len,
                  "max_slots": max_slots},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_paged(n_requests=None, max_slots=None, dim=None,
                        heads=None, layers_n=None, vocab=None,
                        max_len=None, block_tokens=None,
                        budget_tokens=None, spec_draft_len=None):
    """Paged-KV acceptance trace (ISSUE 7): the SAME fixed-seed Poisson
    trace of short requests runs three times at ONE fixed KV HBM budget
    (`budget_tokens` cached tokens per layer):

      slab  — the pre-paging concurrency wall: a [S, max_len] slab at
              this budget holds floor(budget/max_len) slots, each
              paying max_len whether the request needs it or not
              (emulated exactly: max_slots = that floor, pool =
              worst-case blocks per slot);
      paged — the block pool shares budget/block_tokens fixed-size
              blocks across many slots; admission reserves each
              request's OWN worst case (ceil((T0+max_new)/Bt)), so
              resident slots scale with actual tokens;
      spec  — paged + self-drafting speculative decoding
              (`spec_draft_len`-token verify windows, one compiled
              verify step).

    The row reports peak resident slots both ways (the acceptance
    inequality: paged > slab at the same budget — pinned by
    tests/test_bench_protocol.py), speculative accept-rate, and
    tokens/s for each mode. Greedy outputs must be token-identical
    across all three runs (hard raise in-bench: paging and speculation
    must never change WHAT a request decodes to, only when/where).
    Peak-resident, accept-rate, and compile counts are deterministic
    offline; the tokens/s contrast is only meaningful on-chip."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingEngine

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: exercises all three engine modes in seconds
        dim, heads, layers_n = dim or 64, heads or 4, layers_n or 2
        vocab, max_len = vocab or 256, max_len or 96
        n_requests = n_requests or 10
        max_slots = max_slots or 8
        block_tokens = block_tokens or 8
        budget_tokens = budget_tokens or 2 * (max_len or 96)
        spec_draft_len = spec_draft_len or 4
        t_lo, t_hi, n_lo, n_hi, rate = 4, 12, 6, 14, 3.0
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests = n_requests or 64
        max_slots = max_slots or 32
        block_tokens = block_tokens or 16
        budget_tokens = budget_tokens or 8 * (max_len or 1024)
        spec_draft_len = spec_draft_len or 4
        t_lo, t_hi, n_lo, n_hi, rate = 32, 128, 32, 96, 2.0
        dtype = jnp.bfloat16

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = [
        (
            rng.randint(0, vocab,
                        int(rng.randint(t_lo, t_hi + 1))).astype(np.int32),
            int(rng.randint(n_lo, n_hi + 1)),
        )
        for _ in range(n_requests)
    ]
    # the slab wall at this budget: floor(budget/max_len) slots, each
    # paying max_len (the [MAX_SLOTS, max_len] allocation PR 7 removed)
    slab_slots = max(1, int(budget_tokens) // int(max_len))
    pool_blocks = int(budget_tokens) // int(block_tokens)

    def run_once(slots, blocks, spec):
        eng = ServingEngine(
            params, cfg, max_slots=slots, kv_block_tokens=block_tokens,
            kv_pool_blocks=blocks, spec_draft_len=spec)
        hs, peak, peak_blocks = [], 0, 0
        t0 = time.time()
        i = step = 0
        while i < n_requests or eng.live_slots or eng.queue_depth \
                or eng.prefilling_slots:
            while i < n_requests and arrive_at[i] <= step:
                p, n = reqs[i]
                hs.append(eng.submit(p, n))
                i += 1
            if not eng.step() and i < n_requests:
                step = max(step + 1, int(arrive_at[i]))  # idle gap: jump
                continue
            peak = max(peak, eng.live_slots + eng.prefilling_slots)
            peak_blocks = max(peak_blocks, eng.kv_blocks_in_use)
            step += 1
        wall = time.time() - t0
        return eng, wall, peak, peak_blocks, [list(h.tokens) for h in hs]

    eng_slab, wall_slab, peak_slab, _, out_slab = run_once(
        slab_slots, None, None)
    eng_paged, wall_paged, peak_paged, pk_blocks, out_paged = run_once(
        max_slots, pool_blocks, None)
    eng_spec, wall_spec, peak_spec, _, out_spec = run_once(
        max_slots, pool_blocks, spec_draft_len)
    # paging/speculation must never change what any request decodes to
    # — a hard raise, not a bare assert: the gate must survive -O
    if out_paged != out_slab or out_spec != out_slab:
        raise RuntimeError("paged/speculative run changed greedy outputs")
    rep_paged = eng_paged.metrics.report()
    rep_spec = eng_spec.metrics.report()
    toks = rep_paged["tokens_out"]
    return {
        # the acceptance inequality: resident slots at ONE KV budget
        "slots_resident_slab": peak_slab,
        "slots_resident_paged": peak_paged,
        "slots_resident_spec": peak_spec,
        "kv_budget_tokens": int(budget_tokens),
        "kv_pool_blocks": pool_blocks,
        "kv_block_tokens": int(block_tokens),
        "peak_kv_blocks_in_use": pk_blocks,
        "kv_frag_tokens_last": rep_paged["kv_frag_tokens"],
        "kv_tail_blocks_freed": rep_paged["kv_tail_blocks_freed"],
        "cow_blocks": rep_paged["cow_blocks"],
        "spec_draft_len": int(spec_draft_len),
        "spec_accept_rate": rep_spec["spec_accept_rate"],
        "spec_windows": rep_spec["spec_windows"],
        "tokens_out": toks,
        "tokens_per_sec_slab": round(toks / wall_slab, 1),
        "tokens_per_sec_paged": round(toks / wall_paged, 1),
        "tokens_per_sec_spec": round(toks / wall_spec, 1),
        "decode_steps_paged": rep_paged["decode_steps"],
        "decode_steps_spec": rep_spec["decode_steps"],
        "decode_traces_paged": rep_paged["decode_traces"],
        "spec_verify_traces":
            eng_spec.metrics.trace_counts.get("spec_verify", 0),
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0)" % rate,
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_paged_kernel(n_requests=None, max_slots=None, dim=None,
                               heads=None, layers_n=None, vocab=None,
                               max_len=None, block_tokens=None,
                               chunk_tokens=None, cache_tokens=None,
                               spec_draft_len=None):
    """Fused paged-attention kernel acceptance trace (ISSUE 13): the
    SAME fixed-seed Poisson shared-header trace runs twice — once with
    `paged_kernel="gather"` (the XLA `_paged_view` form: a transient
    gathered view [S, MAXB*Bt, H, Dh] per layer per step) and once
    with `paged_kernel="fused"` (parallel/paged_attention.py: Pallas
    kernels that walk the block table inside the kernel) — through the
    full reuse surface: prefix aliasing + publish boundaries, chunked
    prefill, copy-on-write, and self-drafting speculative decoding.

    Hard raises (the acceptance gates, armed in-bench so they survive
    -O): any greedy output divergence between the runs; any
    `_paged_view` call observed DURING the fused run (counted via a
    wrapper — the fused steps must attend through the table, zero
    gathers); decode and spec-verify not traced exactly once per
    engine.

    CPU columns (deterministic offline): step/trace counts, prefill
    tokens, accept rate, the zero-gather count. tokens/s both ways is
    reported but ON-CHIP-PENDING: on CPU the fused kernel runs
    INTERPRETED (resolve_interpret), so the wall-clock contrast is
    meaningless until the kernel compiles to Mosaic on a v5e — the
    measurement slot is reserved in PERF.md's PR 13 section."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingEngine

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: both engines compile + drain in seconds
        dim, heads, layers_n = dim or 64, heads or 4, layers_n or 2
        vocab, max_len = vocab or 256, max_len or 96
        n_requests = n_requests or 8
        max_slots = max_slots or 4
        block_tokens = block_tokens or 8
        chunk_tokens = chunk_tokens or 16
        cache_tokens = cache_tokens or 256
        spec_draft_len = spec_draft_len or 4
        header_len, t_lo, t_hi, n_lo, n_hi, rate = 12, 2, 10, 5, 12, 2.0
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests = n_requests or 64
        max_slots = max_slots or 32
        block_tokens = block_tokens or 16
        chunk_tokens = chunk_tokens or 128
        cache_tokens = cache_tokens or 8192
        spec_draft_len = spec_draft_len or 4
        header_len, t_lo, t_hi, n_lo, n_hi, rate = 128, 32, 128, 32, 96, 2.0
        dtype = jnp.bfloat16

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    header = rng.randint(0, vocab, header_len).astype(np.int32)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = [
        (
            np.concatenate([header, rng.randint(
                0, vocab, int(rng.randint(t_lo, t_hi + 1))
            ).astype(np.int32)]),
            int(rng.randint(n_lo, n_hi + 1)),
        )
        for _ in range(n_requests)
    ]

    def run_once(pk, spec):
        eng = ServingEngine(
            params, cfg, max_slots=max_slots,
            kv_block_tokens=block_tokens,
            prefill_chunk_tokens=chunk_tokens,
            prefix_cache_tokens=cache_tokens,
            spec_draft_len=spec, paged_kernel=pk)
        hs = []
        t0 = time.time()
        i = step = 0
        while i < n_requests or eng.live_slots or eng.queue_depth \
                or eng.prefilling_slots:
            while i < n_requests and arrive_at[i] <= step:
                p, n = reqs[i]
                hs.append(eng.submit(p, n, publish_len=header_len))
                i += 1
            if not eng.step() and i < n_requests:
                step = max(step + 1, int(arrive_at[i]))  # idle gap: jump
                continue
            step += 1
        wall = time.time() - t0
        return eng, wall, [list(h.tokens) for h in hs]

    # two pairs: plain decode (the decode kernel) and speculative
    # (the verify kernel) — spec replaces the plain decode step
    # entirely, so one engine can never trace both
    eng_g, wall_g, out_g = run_once("gather", None)
    eng_gs, _, out_gs = run_once("gather", spec_draft_len)

    # count every _paged_view gather the fused runs perform — the
    # fused steps must attend THROUGH the table, so this must be 0
    views = {"n": 0}
    orig_view = tlm._paged_view

    def _counting_view(*a, **kw):
        views["n"] += 1
        return orig_view(*a, **kw)

    tlm._paged_view = _counting_view
    try:
        eng_f, wall_f, out_f = run_once("fused", None)
        eng_fs, wall_fs, out_fs = run_once("fused", spec_draft_len)
    finally:
        tlm._paged_view = orig_view

    # the acceptance gates — hard raises, not asserts (must survive -O)
    if out_f != out_g or out_fs != out_g or out_gs != out_g:
        raise RuntimeError(
            "fused paged kernel changed greedy outputs vs gather")
    if views["n"]:
        raise RuntimeError(
            "fused run materialised %d _paged_view gathers (must be 0)"
            % views["n"])
    rep_g, rep_f = eng_g.metrics.report(), eng_f.metrics.report()
    rep_fs = eng_fs.metrics.report()
    for eng, pk in ((eng_g, "gather"), (eng_f, "fused")):
        if eng.metrics.report()["decode_traces"] != 1:
            raise RuntimeError(
                "%s run broke the one-compiled-step discipline: %r"
                % (pk, eng.metrics.trace_counts))
    for eng, pk in ((eng_gs, "gather+spec"), (eng_fs, "fused+spec")):
        if eng.metrics.trace_counts.get("spec_verify", 0) != 1:
            raise RuntimeError(
                "%s run broke the one-compiled-step discipline: %r"
                % (pk, eng.metrics.trace_counts))
    toks = rep_f["tokens_out"]
    return {
        "paged_view_calls_fused": views["n"],  # the gather-tax gate: 0
        "decode_steps_gather": rep_g["decode_steps"],
        "decode_steps_fused": rep_f["decode_steps"],
        "decode_traces_fused": rep_f["decode_traces"],
        "spec_verify_traces_fused":
            eng_fs.metrics.trace_counts.get("spec_verify", 0),
        "decode_steps_fused_spec": rep_fs["decode_steps"],
        "prefill_traces_fused": rep_f["prefill_traces"],
        "prefill_tokens_computed": rep_f["prefill_tokens_computed"],
        "spec_accept_rate_fused": rep_fs["spec_accept_rate"],
        "cow_blocks_fused": rep_f["cow_blocks"],
        "tokens_out": toks,
        # on-chip-pending on CPU: the fused kernel runs interpreted
        # here — only the compiled Mosaic contrast means anything
        # (PERF.md PR 13 reserves the v5e slot)
        "tokens_per_sec_gather": round(toks / wall_g, 1),
        "tokens_per_sec_fused": round(toks / wall_f, 1),
        "tokens_per_sec_fused_spec": round(toks / wall_fs, 1),
        "tokens_per_sec_note": "on-chip-pending (fused is interpreted "
                               "on CPU)" if cpu else "compiled",
        "paged_kernel_gather": rep_g["paged_kernel"],
        "paged_kernel_fused": rep_f["paged_kernel"],
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0)" % rate,
        "knobs": {"kv_block_tokens": block_tokens,
                  "prefill_chunk_tokens": chunk_tokens,
                  "prefix_cache_tokens": cache_tokens,
                  "spec_draft_len": spec_draft_len,
                  "max_slots": max_slots},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def _kv_block_bytes(layers_n, heads, dh, block_tokens, kv_quant,
                    act_itemsize):
    """One physical KV block's HBM bytes at a storage dtype — the
    bench's fixed BYTE budget must price blocks exactly as the engine
    does, so this delegates to THE one formula
    (models/transformer.kv_block_bytes, also behind
    engine.kv_block_bytes and bench_offline's roofline)."""
    from paddle_tpu.models.transformer import kv_block_bytes

    return kv_block_bytes(layers_n, heads, dh, block_tokens, kv_quant,
                          act_itemsize=act_itemsize)


def _greedy_agreement(outs, ref):
    """Mean per-request prefix agreement of greedy outputs vs the
    reference run: longest common prefix over the longer length. 1.0
    = token-identical; a first-token flip on every request ~0. The
    serving_quant quality gate's metric — prefix-based because greedy
    decode is autoregressive (one flipped token reshapes everything
    after it, so position-wise matching would punish the tail twice)."""
    num = den = 0
    for a, b in zip(outs, ref):
        m = 0
        while m < min(len(a), len(b)) and a[m] == b[m]:
            m += 1
        num += m
        den += max(len(a), len(b))
    return num / den if den else 1.0


# the serving_quant quality gates: minimum mean greedy-prefix
# agreement vs the f32 run on the fixed-seed smoke trace, per variant
# — a hard raise below the floor (speed must never silently buy
# wrongness; tests/test_bench_protocol.py pins the gates stay armed).
# Floors sit under the measured smoke values by a margin that absorbs
# low-bit format drift but catches wiring bugs (a wrong scale or a
# sign error craters agreement toward ~0.1): int8 KV carries 8-bit
# codes (measured 0.93 on the 2-layer toy — near-lossless on real
# logit margins, the LLM.int8/KVQuant result); fp8 e4m3 has 3
# mantissa bits (~6% relative error, measured 0.75 — the toy model's
# tiny logit margins flip early and prefix agreement compounds);
# weight-int8 perturbs EVERY matmul, not just the cache (measured
# 0.78). 'none' IS the reference: anything under exact 1.0 means the
# baseline run stopped being the baseline.
QUANT_AGREEMENT_GATES = {
    "none": 1.0,
    "int8": 0.85,
    "fp8": 0.60,
    "weight_int8": 0.70,
}


def bench_serving_quant(n_requests=None, max_slots=None, dim=None,
                        heads=None, layers_n=None, vocab=None,
                        max_len=None, block_tokens=None,
                        chunk_tokens=None, cache_tokens=None,
                        budget_bytes=None, agreement_gate=None):
    """Quantized-serving acceptance trace (ISSUE 14): the SAME
    fixed-seed Poisson shared-header trace runs at ONE fixed KV HBM
    BYTE budget with kv_quant = none / int8 / fp8 (each variant gets
    budget_bytes // block_bytes(variant) pool blocks — int8/fp8 blocks
    cost ~1/4 the bytes, so they hold ~4x the blocks), plus a
    weight-quantized run (weight_quant='int8' at the f32 KV pool), all
    through the full reuse surface: prefix aliasing + publish
    boundaries, chunked prefill, and copy-on-write.

    Hard raises (the acceptance gates, armed in-bench so they survive
    -O): int8 KV must hold STRICTLY more resident slots than f32 at
    the byte budget; every variant's mean greedy-prefix agreement vs
    the f32 run must meet its QUANT_AGREEMENT_GATES floor (override
    every floor at once with `agreement_gate`) — the quality gate
    that keeps the byte saving from silently buying wrongness; and
    the one-compiled-step discipline must survive quantization
    (decode traced exactly once per engine).

    CPU columns (deterministic offline): slots-resident,
    bytes-per-resident-token, pool blocks at the budget, agreement,
    trace counts. tokens/s per variant is reported but
    ON-CHIP-PENDING: the HBM-bandwidth win quantization exists for is
    only measurable on a real chip (PERF.md PR 14 reserves the v5e
    slot next to PR 13's)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingEngine

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: four engines compile + drain in seconds
        dim, heads, layers_n = dim or 64, heads or 4, layers_n or 2
        vocab, max_len = vocab or 256, max_len or 96
        n_requests = n_requests or 10
        max_slots = max_slots or 8
        block_tokens = block_tokens or 8
        chunk_tokens = chunk_tokens or 16
        cache_tokens = cache_tokens or 256
        header_len, t_lo, t_hi, n_lo, n_hi, rate = 12, 2, 10, 5, 12, 2.0
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests = n_requests or 64
        max_slots = max_slots or 32
        # int8/fp8 pools want 32-row blocks on the fused Mosaic path
        # (int8 sublane tile) — harmless for the others
        block_tokens = block_tokens or 32
        chunk_tokens = chunk_tokens or 128
        cache_tokens = cache_tokens or 8192
        header_len, t_lo, t_hi, n_lo, n_hi, rate = 128, 32, 128, 32, 96, 2.0
        dtype = jnp.bfloat16

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    dh = dim // heads
    act_item = jnp.dtype(dtype).itemsize
    # ONE byte budget for every variant (default: ONE f32 slab slot's
    # worth of blocks — tight enough that the f32 run queues on the
    # pool while int8's ~4x blocks keep admitting)
    f32_block_bytes = _kv_block_bytes(layers_n, heads, dh, block_tokens,
                                      "none", act_item)
    if budget_bytes is None:
        budget_bytes = (max_len // block_tokens) * f32_block_bytes
    budget_bytes = int(budget_bytes)
    rng = np.random.RandomState(0)
    header = rng.randint(0, vocab, header_len).astype(np.int32)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = [
        (
            np.concatenate([header, rng.randint(
                0, vocab, int(rng.randint(t_lo, t_hi + 1))
            ).astype(np.int32)]),
            int(rng.randint(n_lo, n_hi + 1)),
        )
        for _ in range(n_requests)
    ]

    variants = ["none", "int8", "fp8"]

    def run_once(kvq, wq=None):
        bb = _kv_block_bytes(layers_n, heads, dh, block_tokens, kvq,
                             act_item)
        blocks = max(1, budget_bytes // bb)
        eng = ServingEngine(
            params, cfg, max_slots=max_slots,
            kv_block_tokens=block_tokens, kv_pool_blocks=blocks,
            prefill_chunk_tokens=chunk_tokens,
            prefix_cache_tokens=cache_tokens,
            kv_quant=kvq, weight_quant=wq)
        hs, peak = [], 0
        t0 = time.time()
        i = step = 0
        while i < n_requests or eng.live_slots or eng.queue_depth \
                or eng.prefilling_slots:
            while i < n_requests and arrive_at[i] <= step:
                p, n = reqs[i]
                hs.append(eng.submit(p, n, publish_len=header_len))
                i += 1
            if not eng.step() and i < n_requests:
                step = max(step + 1, int(arrive_at[i]))  # idle gap: jump
                continue
            peak = max(peak, eng.live_slots + eng.prefilling_slots)
            step += 1
        wall = time.time() - t0
        return eng, wall, peak, blocks, bb, [list(h.tokens) for h in hs]

    ref_out = None
    rep = {}
    for name in variants + ["weight_int8"]:
        if name == "weight_int8":
            eng, wall, peak, blocks, bb, outs = run_once("none",
                                                         wq="int8")
        else:
            eng, wall, peak, blocks, bb, outs = run_once(name)
        if ref_out is None:  # the f32 baseline runs first
            ref_out = outs
        m = eng.metrics.report()
        ag = _greedy_agreement(outs, ref_out)
        # the quality gate — a hard raise, not an assert (must
        # survive -O): quantization may trade low bits, never the
        # trace's gross shape
        gate = QUANT_AGREEMENT_GATES[name] if agreement_gate is None \
            else float(agreement_gate)
        if ag < gate:
            raise RuntimeError(
                "serving_quant quality gate: %s agreement %.4f < %.2f "
                "vs the f32 run" % (name, ag, gate))
        if m["decode_traces"] != 1:
            raise RuntimeError(
                "%s run broke the one-compiled-step discipline: %r"
                % (name, eng.metrics.trace_counts))
        toks = m["tokens_out"]
        rep[name] = {
            "slots_resident": peak,
            "kv_pool_blocks": blocks,
            "kv_block_bytes": bb,
            "bytes_per_resident_token": round(bb / block_tokens, 2),
            "agreement_vs_f32": round(ag, 4),
            "agreement_gate": gate,
            "tokens_out": toks,
            "tokens_per_sec": round(toks / wall, 1),
            "prefix_hits": eng.prefix_cache.stats()["hits"],
            "cow_blocks": m["cow_blocks"],
            "kv_quant": m["kv_quant"],
            "weight_quant": m["weight_quant"],
        }
    # the residency inequality int8 > f32 at ONE byte budget — the
    # whole point of the PR; strictly more resident slots or the row
    # is lying about the multiplier
    if rep["int8"]["slots_resident"] <= rep["none"]["slots_resident"]:
        raise RuntimeError(
            "int8 KV did not hold more resident slots than f32 at the "
            "fixed byte budget: %d <= %d"
            % (rep["int8"]["slots_resident"],
               rep["none"]["slots_resident"]))
    # the default path must stay the default path: kv_quant='none'
    # reports no quantization (its token identity vs the pre-quant
    # tree is pinned by the tier-1 engine/kernel suites)
    if rep["none"]["kv_quant"] != "none":
        raise RuntimeError("f32 baseline ran quantized: %r" % rep["none"])
    return {
        "variants": rep,
        "agreement_gates": dict(QUANT_AGREEMENT_GATES),
        "kv_budget_bytes": budget_bytes,
        "kv_block_tokens": int(block_tokens),
        "pool_multiplier_int8": round(
            rep["int8"]["kv_pool_blocks"] / rep["none"]["kv_pool_blocks"],
            2),
        "tokens_per_sec_note": "on-chip-pending (the HBM-bandwidth win "
                               "needs a chip; PERF.md PR 14 reserves "
                               "the v5e slot)" if cpu else "compiled",
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0)" % rate,
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len,
                  "dtype": str(jnp.dtype(dtype))},
    }


def bench_serving_fleet(n_replicas=None, n_requests=None, families=None,
                        header_len=None, family_len=None, max_slots=None,
                        dim=None, heads=None, layers_n=None, vocab=None,
                        max_len=None, chunk_tokens=None, block_tokens=None,
                        cache_tokens=None, kill_replica=0):
    """Serving-fleet acceptance trace (ISSUE 6): the SAME fixed-seed
    Poisson shared-header trace runs through (a) a single-replica
    fleet (the N=1 baseline row), (b) an N-replica fleet with prefix
    AFFINITY routing and a kill drill — replica `kill_replica` is
    killed mid-trace once a third of the paced requests completed —
    and (c) an N-replica fleet with affinity OFF (undisturbed). The
    deterministic offline columns: requests lost (MUST be 0 — the
    drill's whole point), duplicate completions (must be 0), and
    failovers (must be 1 in the drill). The fleet-wide prefix reuse
    contrast (tokens saved / prefill tokens computed, affinity on vs
    off) is REPORTED but timing-dependent: least-loaded routing under
    concurrent load depends on replica-thread scheduling, and the
    kill erases one replica's pool mid-trace — the strict on>off
    inequality is pinned by the no-kill drill in
    tests/test_serving_fleet.py instead.
    Outputs must be token-identical across all three runs (hard raise
    in-bench: neither replication, routing, nor failover may change
    what a request decodes to). tokens/s and the speedup-vs-N×1 ratio
    are only meaningful on-chip — on CPU the replica threads share the
    GIL and one chip's compute, like every serving row here. A warm
    wave (one request per family, concurrent) precedes the paced trace
    so compiles and pool publication happen before measurement starts,
    matching the steady state the fleet serves in."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingFleet

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: 3 fleets' worth of tiny engines, seconds each
        dim, heads, layers_n = dim or 64, heads or 4, layers_n or 2
        vocab, max_len = vocab or 256, max_len or 128
        n_replicas = n_replicas or 3
        n_requests, families = n_requests or 12, families or 3
        header_len, family_len = header_len or 16, family_len or 8
        max_slots = max_slots or 2
        t_lo, t_hi, n_lo, n_hi, rate = 3, 8, 4, 10, 0.5
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_replicas = n_replicas or 3
        n_requests, families = n_requests or 48, families or 3
        header_len, family_len = header_len or 256, family_len or 64
        max_slots = max_slots or 8
        t_lo, t_hi, n_lo, n_hi, rate = 16, 64, 32, 128, 0.5
        dtype = jnp.bfloat16
    chunk_tokens = chunk_tokens or max(16, header_len // 2)
    block_tokens = block_tokens or max(4, header_len // 4)
    cache_tokens = cache_tokens or 4 * (header_len + family_len)
    pub = header_len + family_len

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    header = rng.randint(0, vocab, header_len).astype(np.int32)
    fam = [rng.randint(0, vocab, family_len).astype(np.int32)
           for _ in range(families)]
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    # warm wave: one request per family (published headers + compiled
    # buckets), then the paced Poisson trace
    warm = []
    for f in range(families):
        tail = rng.randint(0, vocab,
                           int(rng.randint(t_lo, t_hi + 1))).astype(np.int32)
        warm.append((np.concatenate([header, fam[f], tail]),
                     int(rng.randint(n_lo, n_hi + 1))))
    reqs = []
    for _ in range(n_requests):
        f = int(rng.randint(families))
        tail = rng.randint(0, vocab,
                           int(rng.randint(t_lo, t_hi + 1))).astype(np.int32)
        reqs.append((np.concatenate([header, fam[f], tail]),
                     int(rng.randint(n_lo, n_hi + 1))))

    def run_once(n_reps, affinity, kill_at=None):
        fleet = ServingFleet(
            params, cfg, n_replicas=n_reps, affinity=affinity,
            heartbeat_timeout_s=120.0,
            max_pending=2 * (n_requests + families),
            engine_kw={"max_slots": max_slots,
                       "prefill_chunk_tokens": chunk_tokens,
                       "prefix_cache_tokens": cache_tokens,
                       "prefix_block_tokens": block_tokens})
        try:
            ws = [fleet.submit(p, n, publish_len=pub) for p, n in warm]
            for h in ws:
                h.result(timeout=600)
            t0 = time.time()
            hs, i, step, killed = [], 0, 0, False
            while True:
                while i < n_requests and arrive_at[i] <= step:
                    p, n = reqs[i]
                    hs.append(fleet.submit(p, n, publish_len=pub))
                    i += 1
                if kill_at is not None and not killed \
                        and sum(h.done for h in hs) >= kill_at:
                    fleet.kill_replica(kill_replica)
                    killed = True
                if i >= n_requests and all(h.done for h in hs):
                    break
                time.sleep(0.004)
                step += 1
            for h in hs:
                h.result(timeout=600)  # raises if anything was lost
            wall = time.time() - t0
            time.sleep(0.2)  # final replica-stats sync
            st = fleet.stats()
            toks = sum(len(h.tokens) for h in hs)
            return st, [list(h.tokens) for h in ws + hs], toks / wall
        finally:
            fleet.close()

    st_1, out_1, tps_1 = run_once(1, affinity=True)
    kill_at = max(1, n_requests // 3)
    st_on, out_on, tps_on = run_once(n_replicas, affinity=True,
                                     kill_at=kill_at)
    st_off, out_off, tps_off = run_once(n_replicas, affinity=False)
    if not (out_1 == out_on == out_off):
        raise RuntimeError(
            "fleet outputs diverge across replication/affinity/kill runs")
    if st_on["lost"] or st_off["lost"] or st_1["lost"]:
        raise RuntimeError("fleet lost requests: %r" % (
            (st_1["lost"], st_on["lost"], st_off["lost"]),))
    return {
        # the drill columns (deterministic offline): nothing lost,
        # nothing double-answered, exactly one failover
        "requests_lost": st_on["lost"],
        "duplicate_completions": st_on["duplicate_refused"],
        "failovers": st_on["failovers"],
        "resubmitted": st_on["resubmitted"],
        "completed": st_on["completed"],
        # fleet-wide prefix reuse: affinity keeps families hot
        "prefix_tokens_saved_affinity_on": st_on["prefix_tokens_saved"],
        "prefix_tokens_saved_affinity_off": st_off["prefix_tokens_saved"],
        "prefill_tokens_computed_on": st_on["prefill_tokens_computed"],
        "prefill_tokens_computed_off": st_off["prefill_tokens_computed"],
        "prefix_hit_rate_on": st_on["prefix_hit_rate"],
        "prefix_hit_rate_off": st_off["prefix_hit_rate"],
        # throughput (on-chip meaningful; CPU shares one chip + GIL)
        "tokens_per_sec_single": round(tps_1, 1),
        "tokens_per_sec_fleet": round(tps_on, 1),
        "tokens_per_sec_fleet_no_kill": round(tps_off, 1),
        "speedup_vs_single": round(tps_on / tps_1, 3) if tps_1 else None,
        "ideal_speedup": n_replicas,
        "n_replicas": n_replicas,
        "n_requests": n_requests,
        "kill_drill": {"replica": kill_replica, "after_completed": kill_at},
        "arrival": "poisson(rate=%g/step, seed=0)" % rate,
        "knobs": {"max_slots": max_slots,
                  "prefill_chunk_tokens": chunk_tokens,
                  "prefix_block_tokens": block_tokens,
                  "prefix_cache_tokens": cache_tokens,
                  "publish_len": pub},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_slo(n_replicas=None, n_requests=None, max_slots=None,
                      dim=None, heads=None, layers_n=None, vocab=None,
                      max_len=None, deadline_s=None, slow_window_s=None,
                      slow_step_s=None, slow_factor=None,
                      slow_min_duration_s=None):
    """Request-SLO / gray-failure acceptance trace (ISSUE 8): the SAME
    fixed-seed Poisson trace of INTERACTIVE requests — every one
    carrying a `deadline_s` budget — runs twice through an N-replica
    fleet with gray-failure detection on: (a) healthy, and (b) with
    replica 0 gray-slowed mid-trace (`slow@` fault: it heartbeats on
    every step, each step just stalls `slow_step_s` for
    `slow_window_s` of wall time — invisible to fail-stop detection).
    The deterministic offline columns, hard-raised in-bench:

      * expired requests MUST be 0 in both runs — the gray replica is
        demoted (step-latency EWMA past `slow_factor` x the live
        median, sustained) and its open requests hedged to survivors
        with token-level resume, so no deadline dies on a wedged
        replica;
      * no false demotion in the healthy run (demotions == 0 there;
        the drill run must demote >= 1 and, after the window, PROBE
        and RESTORE the replica under the SAME incarnation — warm
        pool, no fresh spawn);
      * resumed requests re-decode ZERO already-emitted tokens,
        verified from the journal itself: per rid, the concatenation
        of accepted progress deltas equals the done record's tokens —
        a re-decoded token would appear twice;
      * outputs token-identical between the healthy and gray runs
        (neither demotion, hedging, nor resume may change what a
        request decodes to).

    p99 TTFT under the gray replica is pinned within a bounded excess
    of the healthy run's: gray p99 must beat healthy p99 + the slow
    WINDOW — without demotion, work pinned on the gray replica stalls
    the whole window and then restarts from token zero, so its tail
    exceeds healthy by at least the window; with demotion + resume the
    excess is the demotion response time. tokens/s is on-chip-pending
    like every serving row (CPU replicas share one chip + the GIL);
    the drill columns above are deterministic offline."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.fault_injection import FaultInjector
    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import RequestJournal, ServingFleet

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: 2 fleets' worth of tiny engines
        dim, heads, layers_n = dim or 32, heads or 4, layers_n or 2
        vocab, max_len = vocab or 64, max_len or 64
        n_replicas = n_replicas or 2
        n_requests = n_requests or 10
        # slots sized so healthy TTFT is admission-bound, not
        # queue-bound: the p99 tail must measure the GRAY response,
        # not a deliberately undersized batch
        max_slots = max_slots or 6
        t_lo, t_hi, n_lo, n_hi, rate = 4, 10, 12, 20, 0.5
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_replicas = n_replicas or 3
        n_requests = n_requests or 32
        max_slots = max_slots or 8
        t_lo, t_hi, n_lo, n_hi, rate = 16, 64, 32, 96, 0.5
        dtype = jnp.bfloat16
    deadline_s = deadline_s or 60.0
    slow_window_s = slow_window_s or 2.5
    slow_step_s = slow_step_s or 0.25
    slow_factor = slow_factor or 4.0
    slow_min_duration_s = slow_min_duration_s or 0.3

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = []
    for _ in range(n_requests):
        t = int(rng.randint(t_lo, t_hi + 1))
        reqs.append((rng.randint(0, vocab, t).astype(np.int32),
                     int(rng.randint(n_lo, n_hi + 1))))
    # warm waves: EVERY compiled shape the trace can hit, on EVERY
    # replica, before any health judgement (the README sizing rule:
    # never judge a replica mid-first-compile — a compile is one long
    # silent step, indistinguishable from gray slowness from outside).
    # One wave per pow-2 prefill bucket; each wave is n_replicas
    # concurrent requests, which least-loaded routing spreads one per
    # replica, so after the waves the paced trace compiles NOTHING.
    from paddle_tpu.fluid.core.kernels_sequence import bucket_pow2
    warm_buckets = sorted({max(8, bucket_pow2(t))
                           for t in range(t_lo, t_hi + 1)})
    warm_waves = []
    for L in warm_buckets:
        warm_waves.append([
            (rng.randint(0, vocab, L).astype(np.int32), 4)
            for _ in range(n_replicas)])

    def run_once(gray: bool):
        inj = FaultInjector("")  # inert until armed post-warm
        # PADDLE_TPU_KEEP_JOURNAL_DIR: land the journal there and keep
        # it, so tools/lint.sh's protocol gate can replay the bench
        # smoke's journal through `python -m paddle_tpu.analysis
        # journal` after the run
        keep_dir = os.environ.get("PADDLE_TPU_KEEP_JOURNAL_DIR") or None
        if keep_dir is not None:
            os.makedirs(keep_dir, exist_ok=True)
        jpath = tempfile.mktemp(suffix=".jsonl", prefix="slo_journal_",
                                dir=keep_dir)
        fleet = ServingFleet(
            params, cfg, n_replicas=n_replicas, journal_path=jpath,
            heartbeat_timeout_s=120.0, monitor_interval_s=0.05,
            max_pending=2 * (n_requests
                             + sum(len(w) for w in warm_waves)),
            slow_replica_factor=slow_factor,
            slow_min_duration_s=slow_min_duration_s,
            probe_interval_s=0.15,
            engine_kw={"max_slots": max_slots},
            engine_kw_for=lambda i: (
                {"fault_injector": inj} if i == 0 else {}))
        try:
            for wave in warm_waves:
                ws = [fleet.submit(p, n) for p, n in wave]
                for h in ws:
                    h.result(timeout=600)
            time.sleep(0.3)  # EWMAs settle post-compile
            if gray:
                # the gray window opens 2 engine steps into the paced
                # trace: replica 0 keeps heartbeating but every step
                # stalls — the failure heartbeat monitors cannot see
                inj.arm("slow@2:%g/%g" % (slow_window_s, slow_step_s))
            t0 = time.time()
            hs, i, step = [], 0, 0
            while True:
                while i < n_requests and arrive_at[i] <= step:
                    p, n = reqs[i]
                    hs.append(fleet.submit(
                        p, n, slo="interactive", deadline_s=deadline_s))
                    i += 1
                if i >= n_requests and all(h.done for h in hs):
                    break
                time.sleep(0.004)
                step += 1
            for h in hs:
                h.result(timeout=600)  # raises on lost/expired
            wall = time.time() - t0
            restored = True
            if gray:  # after the window: probe -> restore, same incarnation
                deadline = time.monotonic() + slow_window_s + 30.0
                while fleet.stats()["replicas"][0]["state"] != "live":
                    if time.monotonic() >= deadline:
                        restored = False
                        break
                    time.sleep(0.05)
            st = fleet.stats()
            incarnation0 = st["replicas"][0]["incarnation"]
            toks = sum(len(h.tokens) for h in hs)
            ttfts = sorted(h.ttft_s for h in hs if h.ttft_s is not None)
            p99 = (float(np.percentile(ttfts, 99)) if ttfts else None)
        finally:
            fleet.close()
        # journal audit: every progress token appears EXACTLY once in
        # its rid's done record — a resumed request that re-decoded an
        # already-emitted token would journal it twice and fail here
        done_toks, prog_toks, sources = {}, {}, {}
        for rec in RequestJournal._read(jpath):
            if rec["kind"] == "done":
                done_toks[rec["rid"]] = rec["tokens"]
            elif rec["kind"] == "progress":
                prog_toks.setdefault(rec["rid"], []).extend(rec["tokens"])
                sources.setdefault(rec["rid"], set()).add(
                    (rec["replica"], rec["incarnation"], rec["gen"]))
        if keep_dir is None:
            os.unlink(jpath)
        for rid, toks_done in done_toks.items():
            if prog_toks.get(rid, []) != toks_done:
                raise RuntimeError(
                    "rid %d: journaled progress %r != done tokens %r "
                    "(a resumed request re-decoded emitted tokens?)"
                    % (rid, prog_toks.get(rid), toks_done))
        resumed_rids = sum(1 for s in sources.values() if len(s) > 1)
        return {
            "stats": st, "outputs": [list(h.tokens) for h in hs],
            "p99_ttft_s": p99, "tokens_per_sec": toks / wall,
            "restored": restored, "incarnation0": incarnation0,
            "resumed_rids_journal": resumed_rids,
        }

    healthy = run_once(gray=False)
    gray = run_once(gray=True)
    if healthy["outputs"] != gray["outputs"]:
        raise RuntimeError(
            "outputs diverge between healthy and gray-slow runs: "
            "demotion/hedging/resume changed what a request decodes to")
    hs_st, gr_st = healthy["stats"], gray["stats"]
    for name, st in (("healthy", hs_st), ("gray", gr_st)):
        if st["expired"] or st["expired_on_arrival"]:
            raise RuntimeError(
                "%s run expired %d request(s): the SLO layer failed "
                "its zero-expired bar" % (name, st["expired"]))
        if st["lost"]:
            raise RuntimeError("%s run lost requests: %r" % (name, st))
    if hs_st["demotions"]:
        raise RuntimeError(
            "healthy run demoted a replica (false positive): %r"
            % hs_st["demotions"])
    if not gr_st["demotions"]:
        raise RuntimeError(
            "gray run never demoted the slowed replica: detection "
            "missed a %gs window of %gs steps"
            % (slow_window_s, slow_step_s))
    if not gray["restored"] or gray["incarnation0"] != 1:
        raise RuntimeError(
            "gray replica not restored warm (restored=%r, "
            "incarnation=%r): the demote-probe-restore cycle broke"
            % (gray["restored"], gray["incarnation0"]))
    if not gr_st["resumed_requests"]:
        raise RuntimeError(
            "gray run hedged nothing with token-level resume — the "
            "drill did not exercise the resume path")
    if gray["p99_ttft_s"] is not None and healthy["p99_ttft_s"] is not None \
            and gray["p99_ttft_s"] >= healthy["p99_ttft_s"] + slow_window_s:
        # without demotion, work pinned on the gray replica stalls for
        # the WHOLE window and then re-decodes from scratch — the gray
        # tail would exceed healthy by at least the window. Demotion
        # must keep the excess under it (the demotion response time)
        raise RuntimeError(
            "gray p99 TTFT %.3fs exceeds healthy %.3fs by more than "
            "the %.1fs slow window: demotion failed to bound the tail"
            % (gray["p99_ttft_s"], healthy["p99_ttft_s"], slow_window_s))
    return {
        # the SLO columns (deterministic offline)
        "expired_healthy": hs_st["expired"],
        "expired_gray": gr_st["expired"],
        "requests_lost": gr_st["lost"],
        "demotions_gray": gr_st["demotions"],
        "restores_gray": gr_st["restores"],
        "probes_sent_gray": gr_st["probes_sent"],
        "restored_same_incarnation": gray["incarnation0"] == 1,
        "resumed_requests": gr_st["resumed_requests"],
        "resumed_tokens_reused": gr_st["resumed_tokens"],
        "resumed_rids_journal": gray["resumed_rids_journal"],
        "redecoded_tokens": 0,  # journal-audited above (hard raise)
        # latency columns (wall-clock; tail bounded by demotion)
        "p99_ttft_healthy_s": round(healthy["p99_ttft_s"], 4)
        if healthy["p99_ttft_s"] is not None else None,
        "p99_ttft_gray_s": round(gray["p99_ttft_s"], 4)
        if gray["p99_ttft_s"] is not None else None,
        "p99_ttft_ratio": round(
            gray["p99_ttft_s"] / healthy["p99_ttft_s"], 2)
        if healthy["p99_ttft_s"] and gray["p99_ttft_s"] else None,
        "p99_ttft_excess_bound_s": slow_window_s,
        "tokens_per_sec_healthy": round(healthy["tokens_per_sec"], 1),
        "tokens_per_sec_gray": round(gray["tokens_per_sec"], 1),
        "n_replicas": n_replicas,
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0)" % rate,
        "drill": {"fault": "slow@2:%g/%g" % (slow_window_s, slow_step_s),
                  "replica": 0, "deadline_s": deadline_s},
        "knobs": {"max_slots": max_slots,
                  "slow_replica_factor": slow_factor,
                  "slow_min_duration_s": slow_min_duration_s,
                  "probe_interval_s": 0.15},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_elastic(n_requests=None, max_slots=None, dim=None,
                          heads=None, layers_n=None, vocab=None,
                          max_len=None, deadline_s=None):
    """Disaggregated elastic fleet acceptance (ISSUE 11): the SAME
    fixed-seed Poisson BURST trace — every request carrying a generous
    deadline — runs twice: (a) STATIC, a fixed-size tiered fleet
    (prefill/decode disaggregation, no scaling, no rollout), and (b)
    ELASTIC, the same tiers with the autoscaler on (min 2, max 3
    replicas) plus ONE mid-trace `roll_weights` onto a CRC-verified
    checkpoint of the SAME weights (saved with `save_weights` — the
    pserver push/pull cycle recast as checkpoint promotion). The
    deterministic offline columns, hard-raised in-bench:

      * expired requests MUST be 0 in both runs (the burst rides
        scale-up instead of queue-starving deadlines), and no rid is
        lost or answered twice (`lost == 0`, one `done` per rid in
        the journal);
      * the elastic run must spawn >= 1 replica during the burst,
        retire >= 1 after it (full scale-up -> scale-down cycle),
        migrate >= 1 request from the prefill tier to a decode tier
        at first token, and complete exactly one rollout;
      * NO mixed-version output: the journal replays green through
        the protocol DFA (`--expect-closed`), including the J009
        version fence — every done record's `weights_version` equals
        its latest assignment's;
      * a CORRUPTED candidate checkpoint aborts a second
        `roll_weights` with every live replica still serving the
        rolled version, and the fleet still completing requests;
      * outputs token-identical between the static and elastic runs —
        neither tier migration, autoscaling, nor the weight rollout
        may change what a request decodes to.

    tokens/s is on-chip-pending like every serving row; the drill
    columns above are deterministic offline."""
    import glob
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.protocol_lint import verify_journal
    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import (RequestJournal, RolloutAborted,
                                    ServingFleet, save_weights)

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape
        dim, heads, layers_n = dim or 32, heads or 4, layers_n or 2
        vocab, max_len = vocab or 64, max_len or 64
        n_requests = n_requests or 12
        max_slots = max_slots or 3
        t_lo, t_hi, n_lo, n_hi, rate = 4, 10, 6, 12, 2.0
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests = n_requests or 32
        max_slots = max_slots or 8
        t_lo, t_hi, n_lo, n_hi, rate = 16, 64, 32, 96, 2.0
        dtype = jnp.bfloat16
    deadline_s = deadline_s or 300.0

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    # a BURST: high-rate Poisson arrivals, so open requests outrun the
    # two starting replicas and the scaler has something to answer
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = []
    for _ in range(n_requests):
        t = int(rng.randint(t_lo, t_hi + 1))
        reqs.append((rng.randint(0, vocab, t).astype(np.int32),
                     int(rng.randint(n_lo, n_hi + 1))))

    work_dir = tempfile.mkdtemp(prefix="bench_elastic_")
    ckpt_dir = os.path.join(work_dir, "ckpt")
    # the promotion target: the SAME weights at step 1, written through
    # the training checkpoint machinery (CRC sidecars, atomic commit)
    # so the rollout's verify walk has something real to check — and
    # identical weights keep the output-identity bar meaningful
    save_weights(params, ckpt_dir, step=1)

    tiers = ["prefill", "decode", "decode"]

    def run_once(elastic: bool):
        keep_dir = os.environ.get("PADDLE_TPU_KEEP_JOURNAL_DIR") or None
        if keep_dir is not None:
            os.makedirs(keep_dir, exist_ok=True)
        jpath = tempfile.mktemp(suffix=".jsonl",
                                prefix="elastic_journal_", dir=keep_dir)
        kw = dict(
            n_replicas=2, journal_path=jpath,
            heartbeat_timeout_s=300.0, monitor_interval_s=0.02,
            max_pending=4 * n_requests,
            engine_kw={"max_slots": max_slots},
        )
        if elastic:
            kw.update(replica_tier=tiers, min_replicas=2,
                      max_replicas=3, scale_up_open_per_replica=2,
                      scale_down_idle_s=0.4, scale_cooldown_s=0.05,
                      ckpt_dir=ckpt_dir)
        else:
            kw.update(replica_tier=tiers[:2])
        fleet = ServingFleet(params, cfg, **kw)
        rolled = False
        try:
            t0 = time.time()
            hs, i, step = [], 0, 0
            while True:
                while i < n_requests and arrive_at[i] <= step:
                    p, n = reqs[i]
                    hs.append(fleet.submit(p, n, deadline_s=deadline_s))
                    i += 1
                if elastic and not rolled and i >= n_requests:
                    # the whole burst is in flight (requests run for
                    # many engine steps yet): first let the scaler
                    # answer the queue depth — scale-up is PAUSED
                    # during a rollout, so the cycle under test is
                    # burst -> scale-up -> rolling swap — then roll
                    # while traffic still decodes (drain -> swap ->
                    # refill; in-flight finishes on the old version)
                    gate = time.monotonic() + 60.0
                    while not fleet.stats()["replicas_spawned"]:
                        if time.monotonic() >= gate:
                            raise RuntimeError(
                                "burst never triggered a scale-up "
                                "before the mid-trace rollout")
                        time.sleep(0.01)
                    fleet.roll_weights(ckpt_step=1, timeout=300.0)
                    rolled = True
                if i >= n_requests and all(h.done for h in hs):
                    break
                time.sleep(0.004)
                step += 1
            for h in hs:
                h.result(timeout=600)  # raises on lost/expired
            wall = time.time() - t0
            if elastic:
                # after the burst: sustained low load must retire the
                # extra replica (full scale-up -> scale-down cycle)
                deadline = time.monotonic() + 60.0
                while fleet.stats()["replicas_live"] > 2:
                    if time.monotonic() >= deadline:
                        break
                    time.sleep(0.05)
                # corrupted-candidate drill: a torn weight file must
                # abort the rollout with the fleet untouched
                save_weights(params, ckpt_dir, step=2)
                bad = sorted(glob.glob(os.path.join(
                    ckpt_dir, "step_0000000002", "*.npy")))[0]
                with open(bad, "r+b") as fh:
                    fh.seek(12)
                    fh.write(b"\xde\xad\xbe\xef")
                aborted = False
                try:
                    fleet.roll_weights(ckpt_step=2, timeout=300.0)
                except RolloutAborted:
                    aborted = True
                if not aborted:
                    raise RuntimeError(
                        "corrupted candidate checkpoint did NOT abort "
                        "roll_weights")
                st_live = [r for r in fleet.stats()["replicas"]
                           if r["state"] == "live"]
                if any(r["weights_version"] != 1 for r in st_live):
                    raise RuntimeError(
                        "aborted rollout touched the fleet: live "
                        "versions %r != 1"
                        % [r["weights_version"] for r in st_live])
                # ...and the fleet still serves
                h = fleet.submit(reqs[0][0], reqs[0][1])
                post_abort = list(
                    h.result(timeout=600)[len(reqs[0][0]):])
                if post_abort != [int(t) for t in hs[0].tokens]:
                    raise RuntimeError(
                        "post-abort output diverged from the burst "
                        "run's for the same request")
            st = fleet.stats()
        finally:
            fleet.close()
        # journal audit: the protocol DFA replay IS the dedupe and
        # version-fence check — a second done for a rid is J002, a
        # done whose version differs from its latest assignment's is
        # J009, an unterminated rid is J007 (expect_closed)
        done_ver = {rec["rid"]: rec.get("weights_version")
                    for rec in RequestJournal._read(jpath)
                    if rec["kind"] == "done"}
        diags = verify_journal(jpath, expect_closed=True)
        if diags:
            raise RuntimeError(
                "journal audit failed: %s"
                % "; ".join("%s %s" % (d.code, d.message)
                            for d in diags))
        if keep_dir is None:
            os.unlink(jpath)
        if st["expired"] or st["expired_on_arrival"]:
            raise RuntimeError(
                "%s run expired %d request(s)"
                % ("elastic" if elastic else "static", st["expired"]))
        if st["lost"]:
            raise RuntimeError(
                "%s run lost requests: %r"
                % ("elastic" if elastic else "static", st))
        toks = sum(len(h.tokens) for h in hs)
        return {"stats": st, "outputs": [list(h.tokens) for h in hs],
                "versions": sorted(
                    {v for v in done_ver.values() if v is not None}),
                "tokens_per_sec": toks / wall}

    try:
        static = run_once(elastic=False)
        elastic = run_once(elastic=True)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    if static["outputs"] != elastic["outputs"]:
        raise RuntimeError(
            "outputs diverge between the static and elastic runs: "
            "tier migration / scaling / rollout changed what a "
            "request decodes to")
    el = elastic["stats"]
    if not el["replicas_spawned"]:
        raise RuntimeError(
            "the burst never triggered a scale-up: autoscaler dead "
            "or thresholds wrong (%r)" % el["replicas_spawned"])
    if not el["replicas_retired"]:
        raise RuntimeError(
            "the post-burst lull never retired a replica: scale-down "
            "path dead")
    if not el["migrations"]:
        raise RuntimeError(
            "no prefill->decode migration happened on a tiered fleet")
    if el["rollouts_completed"] != 1:
        raise RuntimeError(
            "expected exactly 1 completed rollout, got %r"
            % el["rollouts_completed"])
    if el["rollout_aborts"] != 1:
        raise RuntimeError(
            "expected exactly 1 aborted rollout (the corrupted "
            "candidate drill), got %r" % el["rollout_aborts"])
    return {
        # the elasticity columns (deterministic offline)
        "expired": el["expired"],
        "requests_lost": el["lost"],
        "replicas_spawned": el["replicas_spawned"],
        "replicas_retired": el["replicas_retired"],
        "migrations": el["migrations"],
        "rollouts_completed": el["rollouts_completed"],
        "rollout_aborts": el["rollout_aborts"],
        "weights_version_final": el["weights_version"],
        "done_versions_seen": elastic["versions"],
        "resumed_requests": el["resumed_requests"],
        "resumed_tokens_reused": el["resumed_tokens"],
        "outputs_identical_to_static": True,  # hard-raised above
        "replicas_live_final": el["replicas_live"],
        # latency/throughput (wall-clock; on-chip-pending)
        "tokens_per_sec_static": round(static["tokens_per_sec"], 1),
        "tokens_per_sec_elastic": round(elastic["tokens_per_sec"], 1),
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0), burst" % rate,
        "knobs": {"max_slots": max_slots, "tiers": tiers,
                  "min_replicas": 2, "max_replicas": 3,
                  "scale_up_open_per_replica": 2,
                  "scale_down_idle_s": 0.4, "scale_cooldown_s": 0.05,
                  "rollout_policy": "finish", "deadline_s": deadline_s},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_multitenant(n_requests=None, max_slots=None, dim=None,
                              heads=None, layers_n=None, vocab=None,
                              max_len=None, deadline_s=None):
    """Multi-tenant serving acceptance (ISSUE 12): one fleet, many
    consumers. The fixed-seed trace mixes

      * two WELL-BEHAVED deadline-class tenants (alpha, weight 2, and
        beta, weight 1), each with its own LoRA adapter batched over
        the one base model through the one compiled step;
      * gamma, a third adapter tenant whose requests force the
        2-payload-slot adapter pool to LRU-EVICT (adapters page like
        KV blocks — the paged-adapter column);
      * hog, which BURSTS 6 back-to-back submits against a burst=2
        token bucket mid-trace;
      * zoo, a batch-SLO tenant running image/CTR-style batched
        inference through the EXISTING fluid.Executor path
        (`tenancy.executor_batch_fn`), interleaved with decode by the
        same continuous-batching scheduler.

    Hard raises (the in-bench acceptance bar):

      * zero deadline misses for alpha/beta/gamma (expired == 0 and
        expired_on_arrival == 0) — the hog burst and the zoo lane
        cannot starve the deadline-class tenants;
      * the burst is shed via `TenantQuotaExceeded`, NOT
        `FleetSaturated` (fleet shed == 0), and shed submits are
        NEVER journaled (the journal's submit count is checked);
      * >= 1 adapter-pool eviction (3 adapters through 2 payload
        slots MUST page);
      * every zoo batch result equals the direct Executor run;
      * the journal replays green through the protocol DFA
        (--expect-closed) and every assign/done record carries the
        typed `tenant` side-band;
      * every tenant's outputs are TOKEN-IDENTICAL to a per-tenant
        sequential run (one single-slot engine per tenant, same
        adapter): neither batching N adapters into one step, WFQ
        routing, nor the batch lane changes what any request decodes
        to.

    tokens/s is on-chip-pending like every serving row; the columns
    above are deterministic offline."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.protocol_lint import verify_journal
    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import (AdapterRegistry, RequestJournal,
                                    ServingEngine, ServingFleet,
                                    TenantQuotaExceeded, TenantRegistry,
                                    executor_batch_fn, make_adapter)

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape
        dim, heads, layers_n = dim or 32, heads or 4, layers_n or 2
        vocab, max_len = vocab or 64, max_len or 64
        n_requests = n_requests or 10
        max_slots = max_slots or 3
        t_lo, t_hi, n_lo, n_hi, rate = 4, 10, 4, 8, 1.0
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests = n_requests or 24
        max_slots = max_slots or 8
        t_lo, t_hi, n_lo, n_hi, rate = 16, 64, 16, 48, 1.0
        dtype = jnp.bfloat16
    deadline_s = deadline_s or 300.0

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    areg = AdapterRegistry()
    for name, seed in (("ad_alpha", 1), ("ad_beta", 2), ("ad_gamma", 3)):
        areg.register(name, make_adapter(cfg, rank=4, seed=seed))
    treg = TenantRegistry()
    treg.add("alpha", rate=100.0, burst=100.0, weight=2.0,
             adapter="ad_alpha")
    treg.add("beta", rate=100.0, burst=100.0, weight=1.0,
             adapter="ad_beta")
    treg.add("gamma", rate=100.0, burst=100.0, weight=1.0,
             adapter="ad_gamma")
    treg.add("hog", rate=0.001, burst=2.0, weight=1.0)
    treg.add("zoo", rate=100.0, burst=100.0, weight=1.0, slo="batch")

    # the zoo model: a tiny inference program through the EXISTING
    # fluid Executor path (the reference's save_inference_model
    # serving story) — one fc layer is enough to prove the lane; the
    # real zoo (resnet/vgg/ctr) serves through exactly this surface
    import paddle_tpu.fluid as fluid

    zoo_main, zoo_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(zoo_main, zoo_startup):
        zx = fluid.layers.data(name="zx", shape=[8], dtype="float32")
        zy = fluid.layers.fc(input=zx, size=4, act="softmax")
    zoo_exe = fluid.Executor(fluid.CPUPlace())
    zoo_exe.run(zoo_startup)
    zrng = np.random.RandomState(7)
    zoo_feeds = [{"zx": zrng.rand(4, 8).astype(np.float32)}
                 for _ in range(3)]
    zoo_direct = [zoo_exe.run(zoo_main, feed=f, fetch_list=[zy])[0]
                  for f in zoo_feeds]

    rng = np.random.RandomState(0)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    tenant_of = ["alpha" if i % 2 == 0 else "beta"
                 for i in range(n_requests)]
    # gamma rides the tail: its adapter is the third through a
    # 2-payload-slot pool, so paging MUST evict
    reqs = []
    for _ in range(n_requests + 2):
        t = int(rng.randint(t_lo, t_hi + 1))
        reqs.append((rng.randint(0, vocab, t).astype(np.int32),
                     int(rng.randint(n_lo, n_hi + 1))))
    hog_burst_at = n_requests // 2

    keep_dir = os.environ.get("PADDLE_TPU_KEEP_JOURNAL_DIR") or None
    if keep_dir is not None:
        os.makedirs(keep_dir, exist_ok=True)
    jpath = tempfile.mktemp(suffix=".jsonl",
                            prefix="multitenant_journal_", dir=keep_dir)
    fleet = ServingFleet(
        params, cfg, n_replicas=2, journal_path=jpath,
        heartbeat_timeout_s=300.0, monitor_interval_s=0.02,
        max_pending=8 * (n_requests + 16), tenants=treg,
        engine_kw={"max_slots": max_slots, "adapter_registry": areg,
                   "adapter_slots": 3})
    t0 = time.time()
    by_tenant = {}
    hog_handles, quota_shed, zoo_handles = [], 0, []
    try:
        hs, i, step, burst_done = [], 0, 0, False
        while True:
            while i < n_requests + 2 and (
                    i >= n_requests or arrive_at[min(i, n_requests - 1)]
                    <= step):
                ten = tenant_of[i] if i < n_requests else "gamma"
                p, n = reqs[i]
                h = fleet.submit(p, n, tenant=ten,
                                 deadline_s=deadline_s)
                by_tenant.setdefault(ten, []).append((h, p, n))
                hs.append(h)
                i += 1
            if not burst_done and i >= hog_burst_at:
                # the quota drill: 6 back-to-back submits against a
                # burst=2 bucket — 2 admit, 4 shed as the TENANT's
                # verdict (TenantQuotaExceeded), and the fleet-wide
                # FleetSaturated shed must stay 0
                for _ in range(6):
                    p, n = reqs[0]
                    try:
                        h = fleet.submit(p, n, tenant="hog")
                    except TenantQuotaExceeded:
                        quota_shed += 1
                    else:
                        by_tenant.setdefault("hog", []).append(
                            (h, p, n))
                        hs.append(h)
                # ...and the zoo lane, through the same scheduler
                for f in zoo_feeds:
                    zoo_handles.append(fleet.submit_batch(
                        executor_batch_fn(zoo_exe, zoo_main, f, [zy]),
                        tenant="zoo", cost=8.0))
                burst_done = True
            if i >= n_requests + 2 and burst_done \
                    and all(h.done for h in hs) \
                    and all(h.done for h in zoo_handles):
                break
            time.sleep(0.004)
            step += 1
        for h in hs:
            h.result(timeout=600)  # raises on lost/expired
        for h in zoo_handles:
            h.result(timeout=600)
        wall = time.time() - t0
        st = fleet.stats()
    finally:
        fleet.close()

    if quota_shed != 4:
        raise RuntimeError(
            "hog burst: expected 4 TenantQuotaExceeded sheds "
            "(burst=2 of 6), got %d" % quota_shed)
    if st["shed"] != 0:
        raise RuntimeError(
            "the burst leaked into FleetSaturated (%d): quota must "
            "shed it as the tenant's verdict" % st["shed"])
    if st["expired"] or st["expired_on_arrival"]:
        raise RuntimeError(
            "%d deadline miss(es): the burst/zoo lanes starved a "
            "well-behaved tenant" % (st["expired"]
                                     + st["expired_on_arrival"]))
    if st["lost"]:
        raise RuntimeError("requests lost: %r" % st)
    if st["adapter_evictions"] < 1:
        raise RuntimeError(
            "no adapter-pool eviction: 3 adapters through 2 payload "
            "slots must page (got %r)" % st["adapter_evictions"])
    for got, want in zip([h.batch_result[0] for h in zoo_handles],
                         zoo_direct):
        if not np.allclose(got, want):
            raise RuntimeError(
                "zoo batch-lane result diverged from the direct "
                "Executor run")

    # journal audit: DFA green (exactly-once, typed side-bands,
    # everything terminal) + shed-never-journaled + tenant side-band
    # present on every assign/done
    recs = list(RequestJournal._read(jpath))
    n_submits = sum(1 for r in recs if r["kind"] == "submit")
    n_expected = len(hs) + len(zoo_handles)
    if n_submits != n_expected:
        raise RuntimeError(
            "journal holds %d submits, %d requests were accepted — a "
            "shed submit was journaled (or one was lost)"
            % (n_submits, n_expected))
    for r in recs:
        if r["kind"] == "assign" and "tenant" not in r:
            raise RuntimeError("assign record without tenant side-band")
        if r["kind"] == "done" and r.get("tenant") is None:
            raise RuntimeError("done record without tenant side-band")
    diags = verify_journal(jpath, expect_closed=True)
    if diags:
        raise RuntimeError(
            "journal audit failed: %s"
            % "; ".join("%s %s" % (d.code, d.message) for d in diags))
    if keep_dir is None:
        os.unlink(jpath)

    # per-tenant SEQUENTIAL oracle: one single-slot engine per tenant
    # (same base weights, same adapter) — batching N tenants' adapters
    # into one compiled step must not change any tenant's tokens
    for ten, items in sorted(by_tenant.items()):
        eng = ServingEngine(params, cfg, max_slots=1,
                            adapter_registry=areg, adapter_slots=3)
        seq = [eng.submit(p, n, adapter=treg.get(ten).adapter)
               for _h, p, n in items]
        eng.run()
        for (h, _p, _n), sh in zip(items, seq):
            if list(h.tokens) != list(sh.tokens):
                raise RuntimeError(
                    "tenant %r outputs diverge from its sequential "
                    "run: %r != %r" % (ten, h.tokens, sh.tokens))

    tok_total = sum(len(h.tokens) for h in hs)
    tenants = st["tenants"]
    return {
        # the multi-tenant columns (deterministic offline)
        "deadline_misses_well_behaved": st["expired"]
        + st["expired_on_arrival"],
        "requests_lost": st["lost"],
        "quota_shed": quota_shed,
        "fleet_saturated_shed": st["shed"],
        "hog_admitted": len(by_tenant.get("hog", [])),
        "batch_jobs_completed": st["batch_jobs_completed"],
        "adapter_hits": st["adapter_hits"],
        "adapter_misses": st["adapter_misses"],
        "adapter_evictions": st["adapter_evictions"],
        "adapter_uploads": st["adapter_uploads"],
        "outputs_identical_per_tenant": True,  # hard-raised above
        "zoo_results_match_executor": True,    # hard-raised above
        "per_tenant": {
            t: {"completed": v["completed"],
                "tokens_out": v["tokens_out"],
                "shed_quota": v["shed_quota"],
                "mean_queue_wait_s": v["mean_queue_wait_s"]}
            for t, v in sorted(tenants.items())},
        # latency/throughput (wall-clock; on-chip-pending)
        "tokens_per_sec": round(tok_total / wall, 1),
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0) + hog burst of 6"
        % rate,
        "knobs": {"max_slots": max_slots, "n_replicas": 2,
                  "adapter_slots": 3, "adapter_rank": 4,
                  "weights": {"alpha": 2.0, "beta": 1.0, "gamma": 1.0},
                  "hog_bucket": {"rate": 0.001, "burst": 2},
                  "deadline_s": deadline_s},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def garble_behind_a_canary():
    """A FaultInjector for replica 1 of a canary fleet whose garble@
    begins at a step the DRILL chooses, so that the outcome hangs on
    no thread's timing. A known-answer canary can only vouch for what
    is still open when it is judged: a request that completes on the
    garbled replica before the next canary's verdict is delivered
    garbled. `garble@N` from an arbitrary step raced exactly that
    (short requests against the canary period). Set `.fleet`, then
    `.wanted = True`: the fault begins at a step boundary of the
    replica's own thread (`tick()`) at which its engine holds a canary
    already decoding that still owes a token (the mismatch is certain)
    and a real request decoding too (there IS tainted progress), while
    every real request owes at least two tokens more than the canary
    — a step emits one token a slot, so the canary's verdict rides an
    earlier handshake than any real completion."""
    from paddle_tpu.distributed.fault_injection import FaultInjector

    class _Injector(FaultInjector):
        fleet, wanted, active = None, False, True  # tick at every step

        def tick(self):
            step = FaultInjector.tick(self)
            if self.wanted and not self._garbled:
                # the replica thread's own table (fleet rid -> engine
                # handle; canary rids are negative), read on that thread
                sv = self.fleet._replicas[1]._serving
                owed = lambda sh: sh.max_new_tokens - len(sh.tokens)
                canary = [sh for rid, sh in sv.items()
                          if rid < 0 and sh.tokens and not sh.done]
                real = [sh for rid, sh in sv.items() if rid >= 0]
                self._garbled = (
                    len(canary) == 1 and any(sh.tokens for sh in real)
                    and all(owed(sh) >= owed(canary[0]) + 2
                            for sh in real))
            return step

    return _Injector("")


def bench_serving_integrity(n_requests=None, max_slots=None, dim=None,
                            heads=None, layers_n=None, vocab=None,
                            max_len=None, canary_interval_s=None):
    """Silent-corruption tolerance acceptance (ISSUE 15): the SAME
    fixed-seed shared-header Poisson trace runs three times through a
    2-replica fleet with the full integrity stack armed (in-step
    numeric traps, KV block fingerprints, known-answer canaries,
    auto_refill quarantine):

      clean   no fault — pins the FALSE-POSITIVE bar: zero integrity
              trips, zero canary mismatches, zero fingerprint
              mismatches on a healthy fleet (canaries complete clean)
      garble  replica 1 emits wrong-but-FINITE tokens from mid-trace
              on (garble@, sticky — the SDC shape numeric traps cannot
              see); the next known-answer canary mismatches, the
              replica quarantines with its journaled progress since
              the last clean canary TAINTED, and the taint windows
              re-decode on the healthy survivor
      flip    one resident KV block on replica 1 is corrupted in place
              (flip@, finite garbage); the fingerprint spot-check at
              the next aliased re-open (the shared header keeps
              hitting replica 1 under prefix affinity) catches it

    Hard raises, all deterministic offline: every drill's outputs
    TOKEN-IDENTICAL to the clean run (zero tainted tokens survive into
    final outputs — the falsifiability bar: a single laundered corrupt
    token diverges), the corrupt replica tripped + quarantined EXACTLY
    once per drill with the expected trip kind (canary vs fingerprint)
    and a fresh incarnation (supervisor-backoff refill), zero rids
    lost or duplicated, and every journal green through the protocol
    DFA `--expect-closed` INCLUDING the J010 taint fence — re-decoded
    tokens lie entirely inside journaled taint windows, and nothing
    lands from a quarantined incarnation after its integrity event."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.diagnostics import format_diag
    from paddle_tpu.analysis.protocol_lint import verify_journal
    from paddle_tpu.distributed.fault_injection import FaultInjector
    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingFleet

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: 3 fleets' worth of tiny engines
        dim, heads, layers_n = dim or 32, heads or 4, layers_n or 2
        vocab, max_len = vocab or 64, max_len or 64
        n_requests = n_requests or 8
        max_slots = max_slots or 4
        t_hdr, t_lo, t_hi, n_lo, n_hi, rate = 8, 2, 5, 8, 14, 0.5
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests = n_requests or 24
        max_slots = max_slots or 8
        t_hdr, t_lo, t_hi, n_lo, n_hi, rate = 32, 8, 24, 32, 64, 0.5
        dtype = jnp.bfloat16
    canary_interval_s = canary_interval_s or 0.05
    bt = 4  # small blocks: the shared header publishes whole blocks

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    header = rng.randint(0, vocab, t_hdr).astype(np.int32)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = []
    for _ in range(n_requests):
        tail = rng.randint(0, vocab,
                           rng.randint(t_lo, t_hi + 1)).astype(np.int32)
        reqs.append((np.concatenate([header, tail]),
                     int(rng.randint(n_lo, n_hi + 1))))

    def run_once(fault):
        # inert until armed post-warm; handed to replica 1 ONCE — the
        # quarantine's fresh incarnation composes its engine kwargs
        # again and must come up CLEAN (a sticky garble re-armed on
        # the replacement would just trip it again, forever)
        garble = fault is not None and fault.startswith("garble")
        inj = garble_behind_a_canary() if garble else FaultInjector("")
        armed = {"used": False}

        def kw_for(i):
            if i == 1 and not armed["used"]:
                armed["used"] = True
                return {"fault_injector": inj}
            return {}

        keep_dir = os.environ.get("PADDLE_TPU_KEEP_JOURNAL_DIR") or None
        if keep_dir is not None:
            os.makedirs(keep_dir, exist_ok=True)
        jpath = tempfile.mktemp(suffix=".jsonl",
                                prefix="integrity_journal_",
                                dir=keep_dir)
        fleet = ServingFleet(
            params, cfg, n_replicas=2, journal_path=jpath,
            heartbeat_timeout_s=120.0, monitor_interval_s=0.02,
            max_pending=4 * n_requests, affinity=True,
            auto_refill=True, canary_interval_s=canary_interval_s,
            engine_kw={"max_slots": max_slots, "kv_block_tokens": bt,
                       "prefix_cache_tokens": 32 * bt,
                       "kv_fingerprints": True},
            engine_kw_for=kw_for)
        try:
            # warm both replicas (compiles + seed the shared-header
            # prefix on each pool) and let one clean canary land per
            # replica before any fault: the canary mark is the taint
            # window's left edge, and the drills' windows must open at
            # a VERIFIED index, not at token zero
            w0 = fleet.submit(*reqs[0])
            w1 = fleet.submit(*reqs[1])
            w0.result(timeout=600)
            w1.result(timeout=600)
            deadline = time.monotonic() + 60.0
            while fleet.stats()["canaries_ok"] < 2:
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        "no clean canary within 60s of a warm fleet: "
                        "the canary machinery is broken")
                time.sleep(0.01)
            if garble:
                inj.fleet, inj.wanted = fleet, True
            elif fault is not None:
                inj.arm(fault)  # fires on replica 1's next steps
            t0 = time.time()
            hs, i, step = [], 0, 0
            while True:
                while i < n_requests and arrive_at[i] <= step:
                    hs.append(fleet.submit(*reqs[i]))
                    i += 1
                if i >= n_requests and all(h.done for h in hs):
                    break
                time.sleep(0.004)
                step += 1
            # the garble begins only once a canary finds replica 1
            # mid-decode: repeat the trace's requests until it has
            deadline = time.monotonic() + 120.0
            while garble and not inj.garbled:
                if time.monotonic() >= deadline:
                    raise RuntimeError("no canary met a decoding "
                                       "request on replica 1 in 120s")
                hs.append(fleet.submit(*reqs[len(hs) % n_requests]))
                while not (inj.garbled or hs[-1].done):
                    time.sleep(0.004)
            outs = [list(h.result(timeout=600)) for h in hs]
            wall = time.time() - t0
            if fault is not None:
                # the quarantine must complete: fresh incarnation on
                # the corrupt replica (supervisor-backoff auto-refill)
                deadline = time.monotonic() + 60.0
                while fleet.stats()["replicas"][1]["incarnation"] < 2:
                    if time.monotonic() >= deadline:
                        raise RuntimeError(
                            "tripped replica never refilled under a "
                            "fresh incarnation")
                    time.sleep(0.02)
            st = fleet.stats()
            toks = sum(len(h.tokens) for h in hs)
        finally:
            fleet.close()
        diags = verify_journal(jpath, expect_closed=True)
        if diags:
            raise RuntimeError(
                "journal DFA violations (%s run):\n  %s"
                % (fault or "clean",
                   "\n  ".join(format_diag(d) for d in diags)))
        if keep_dir is None:
            os.unlink(jpath)
        return {"outputs": outs, "stats": st,
                "tokens_per_sec": toks / wall if wall else None}

    clean = run_once(None)
    st = clean["stats"]
    if st["integrity_trips"] or st["canary_mismatches"] \
            or st["fp_mismatches"]:
        raise RuntimeError(
            "clean run tripped the integrity sentinel (false "
            "positive): %r" % {k: st[k] for k in (
                "integrity_trips", "canary_mismatches",
                "fp_mismatches")})
    if not st["canaries_ok"]:
        raise RuntimeError("clean run completed no canaries: the "
                           "known-answer machinery never ran")

    drills = {}
    for name, fault, want_kind in (
            ("garble", "garble@2", "canary"),
            ("flip", "flip@2", "fingerprint")):
        rec = run_once(fault)
        dst = rec["stats"]
        if rec["outputs"] != [clean["outputs"][k % n_requests]
                              for k in range(len(rec["outputs"]))]:
            raise RuntimeError(
                "%s drill outputs diverge from the clean run: a "
                "corrupt token survived quarantine + taint-aware "
                "resume" % name)
        if dst["integrity_trips"] != 1:
            raise RuntimeError(
                "%s drill: expected exactly one integrity trip, got "
                "%r (%r)" % (name, dst["integrity_trips"],
                             dst["integrity_trip_kinds"]))
        if dst["integrity_trip_kinds"].get(want_kind) != 1:
            raise RuntimeError(
                "%s drill tripped via %r, expected kind %r"
                % (name, dst["integrity_trip_kinds"], want_kind))
        if dst["lost"] or dst["duplicate_refused"]:
            raise RuntimeError("%s drill lost/duplicated requests: %r"
                               % (name, dst))
        if dst["replicas"][1]["incarnation"] != 2:
            raise RuntimeError(
                "%s drill: corrupt replica quarantined %d times, "
                "expected exactly once (fresh incarnation == 2)"
                % (name, dst["replicas"][1]["incarnation"] - 1))
        drills[name] = dst

    return {
        # the integrity columns (deterministic offline)
        "trips_clean": st["integrity_trips"],
        "canaries_ok_clean": st["canaries_ok"],
        "trips_garble": drills["garble"]["integrity_trips"],
        "trip_kind_garble": dict(
            drills["garble"]["integrity_trip_kinds"]),
        "tainted_tokens_garble": drills["garble"]["tainted_tokens"],
        "trips_flip": drills["flip"]["integrity_trips"],
        "trip_kind_flip": dict(drills["flip"]["integrity_trip_kinds"]),
        "fp_mismatches_flip": drills["flip"]["fp_mismatches"],
        "requests_lost": max(d["lost"] for d in drills.values()),
        "outputs_identical": True,  # hard-raised above
        "journal_dfa": "green --expect-closed incl. J010 (hard-raised)",
        # honest overhead row (PERF.md): trap+fingerprint+canary cost
        # on the same trace, clean run vs drills — wall-clock, so
        # on-chip-pending like every serving tokens/s column
        "tokens_per_sec_clean": (
            round(clean["tokens_per_sec"], 1)
            if clean["tokens_per_sec"] else None),
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0), %d-token shared "
                   "header" % (rate, t_hdr),
        "drill": {"garble": "garble@2 (replica 1, sticky)",
                  "flip": "flip@2 (replica 1, one resident block)"},
        "knobs": {"max_slots": max_slots, "kv_block_tokens": bt,
                  "canary_interval_s": canary_interval_s,
                  "kv_fingerprints": True, "auto_refill": True},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_kv_handoff(n_requests=None, max_slots=None, dim=None,
                             heads=None, layers_n=None, vocab=None,
                             max_len=None):
    """Durable-KV fleet acceptance (ISSUE 16): the SAME fixed-seed
    shared-header Poisson trace runs four times against ONE tiered
    block store directory (host-RAM/disk spill of closed, quantized,
    fingerprinted KV blocks):

      cold     1 replica, empty store — pins the baseline outputs,
               the cold first-request TTFT/prefill cost, and seeds
               the store (publish-at-retire spill MUST leave >= 1
               durable record behind)
      handoff  2 replicas, prefill/decode tiers, same store — every
               first-token migration ships the finished prefix as a
               checksummed block package; the CLEAN-PATH bar, hard-
               raised: `tokens_recomputed_at_migration == 0` with
               >= 1 migration and >= 1 verified import (re-prefill
               demoted to a counted fallback, not the path)
      kill     3 replicas (prefill + 2 decode), same store — one
               decode replica killed mid-trace; failover may fall
               back to re-prefill (graceful degradation, COUNTED in
               `handoff_fallbacks`) but never changes a token
      warm     a fresh 1-replica fleet on the same store directory —
               the restart warms its prefix trie from the store
               (`store_warm_blocks` >= 1) and serves the first
               shared-header request WITHOUT re-decoding the header
               (strictly fewer prefill tokens than the cold phase's
               first request); warm-vs-cold TTFT is the honest
               latency contrast column

    Hard raises, all deterministic offline: outputs token-identical
    across all four phases, zero rids lost or double-answered, and
    every phase's journal green through the protocol DFA
    `--expect-closed` INCLUDING the J011 handoff fence — every done
    record accounts for the block package its assignment shipped.
    tokens/s and the TTFT contrast are wall-clock (on-chip-pending
    like every serving row)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.diagnostics import format_diag
    from paddle_tpu.analysis.protocol_lint import verify_journal
    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import ServingFleet

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: 4 fleets' worth of tiny engines
        dim, heads, layers_n = dim or 32, heads or 4, layers_n or 2
        vocab, max_len = vocab or 64, max_len or 64
        n_requests = n_requests or 8
        max_slots = max_slots or 4
        t_hdr, t_lo, t_hi, n_lo, n_hi, rate = 8, 2, 5, 8, 14, 0.5
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        n_requests = n_requests or 24
        max_slots = max_slots or 8
        t_hdr, t_lo, t_hi, n_lo, n_hi, rate = 32, 8, 24, 32, 64, 0.5
        dtype = jnp.bfloat16
    bt = 4  # small blocks: the shared header spans >= 2 whole blocks

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    header = rng.randint(0, vocab, t_hdr).astype(np.int32)
    arrive_at = np.floor(
        np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ).astype(int)
    reqs = []
    for _ in range(n_requests):
        tail = rng.randint(0, vocab,
                           rng.randint(t_lo, t_hi + 1)).astype(np.int32)
        reqs.append((np.concatenate([header, tail]),
                     int(rng.randint(n_lo, n_hi + 1))))

    work_dir = tempfile.mkdtemp(prefix="bench_kvhandoff_")
    store_dir = os.path.join(work_dir, "kv_store")

    def run_phase(name, tiers, kill_at=None):
        keep_dir = os.environ.get("PADDLE_TPU_KEEP_JOURNAL_DIR") or None
        if keep_dir is not None:
            os.makedirs(keep_dir, exist_ok=True)
        jpath = tempfile.mktemp(suffix=".jsonl",
                                prefix="kvhandoff_%s_journal_" % name,
                                dir=keep_dir)
        fleet = ServingFleet(
            params, cfg, n_replicas=len(tiers), journal_path=jpath,
            heartbeat_timeout_s=120.0, monitor_interval_s=0.02,
            max_pending=4 * n_requests, affinity=True,
            replica_tier=(tiers if len(tiers) > 1 else None),
            kv_store_dir=store_dir, kv_store_bytes=1 << 20,
            handoff=True,
            engine_kw={"max_slots": max_slots, "kv_block_tokens": bt,
                       "prefix_cache_tokens": 32 * bt,
                       "kv_fingerprints": True})
        try:
            # request 0 runs ALONE first in every phase: its isolated
            # TTFT + prefill-token cost is the cold-vs-warm contrast
            # (same request, same fleet shape, only the store differs)
            h0 = fleet.submit(*reqs[0])
            h0.result(timeout=600)
            pst = fleet.stats()
            probe = {"prefill_tokens": pst["prefill_tokens_computed"],
                     "warm_blocks": pst["store_warm_blocks"],
                     "ttft_s": h0.ttft_s}
            t0 = time.time()
            hs, i, step, killed = [h0], 1, 0, False
            while True:
                while i < n_requests and arrive_at[i] <= step:
                    hs.append(fleet.submit(*reqs[i]))
                    i += 1
                if kill_at is not None and not killed \
                        and sum(h.done for h in hs) >= kill_at:
                    fleet.kill_replica(len(tiers) - 1)
                    killed = True
                if i >= n_requests and all(h.done for h in hs):
                    break
                time.sleep(0.004)
                step += 1
            outs = [list(h.result(timeout=600)) for h in hs]
            wall = time.time() - t0
            st = fleet.stats()
            toks = sum(len(h.tokens) for h in hs)
        finally:
            fleet.close()
        diags = verify_journal(jpath, expect_closed=True)
        if diags:
            raise RuntimeError(
                "journal DFA violations (%s phase):\n  %s"
                % (name, "\n  ".join(format_diag(d) for d in diags)))
        if keep_dir is None:
            os.unlink(jpath)
        if st["lost"] or st["duplicate_refused"]:
            raise RuntimeError("%s phase lost/duplicated requests: %r"
                               % (name, {k: st[k] for k in
                                         ("lost", "duplicate_refused")}))
        return {"outputs": outs, "stats": st, "probe": probe,
                "tokens_per_sec": toks / wall if wall else None}

    try:
        cold = run_phase("cold", ["decode"])
        cst = cold["stats"]
        if not cst["kv_store"] or cst["kv_store"]["records"] < 1:
            raise RuntimeError(
                "cold phase spilled nothing to the block store: "
                "publish-at-retire path dead (%r)" % (cst["kv_store"],))

        handoff = run_phase("handoff", ["prefill", "decode"])
        hst = handoff["stats"]
        if not hst["migrations"]:
            raise RuntimeError(
                "no prefill->decode migration on the tiered fleet: "
                "the handoff path was never exercised")
        if hst["tokens_recomputed_at_migration"] != 0:
            raise RuntimeError(
                "clean handoff phase re-prefilled %d token(s) at "
                "migration — block packages must make the target's "
                "re-prefill count ZERO (imports=%d fallbacks=%d)"
                % (hst["tokens_recomputed_at_migration"],
                   hst["handoff_imports"], hst["handoff_fallbacks"]))
        if not hst["handoff_imports"]:
            raise RuntimeError(
                "clean handoff phase imported no block package "
                "(packages=%d): every migration fell back"
                % hst["handoff_packages"])

        kill_at = max(1, n_requests // 3)
        kill = run_phase("kill", ["prefill", "decode", "decode"],
                         kill_at=kill_at)
        kst = kill["stats"]
        if kst["replicas"][2]["state"] != "dead":
            raise RuntimeError(
                "kill drill: replica 2 still %r after kill_replica"
                % kst["replicas"][2]["state"])

        warm = run_phase("warm", ["decode"])
        wst = warm["stats"]
        if not wst["store_warm_blocks"]:
            raise RuntimeError(
                "restarted fleet warmed zero blocks from the store: "
                "trie warm-start path dead (%r)" % (wst["kv_store"],))
        if warm["probe"]["prefill_tokens"] >= \
                cold["probe"]["prefill_tokens"]:
            raise RuntimeError(
                "warm restart re-decoded the shared header: first "
                "request prefilled %d token(s) vs %d cold — the "
                "store-warmed trie saved nothing"
                % (warm["probe"]["prefill_tokens"],
                   cold["probe"]["prefill_tokens"]))

        for name, rec in (("handoff", handoff), ("kill", kill),
                          ("warm", warm)):
            if rec["outputs"] != cold["outputs"]:
                raise RuntimeError(
                    "%s phase outputs diverge from the cold baseline: "
                    "a transferred/spilled block changed what a "
                    "request decodes to" % name)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    return {
        # the durability columns (deterministic offline)
        "store_records_after_cold": cst["kv_store"]["records"],
        "store_spilled_blocks": cst["store_spilled_blocks"],
        "migrations_handoff": hst["migrations"],
        "handoff_packages": hst["handoff_packages"],
        "handoff_imports": hst["handoff_imports"],
        "handoff_blocks_imported": hst["handoff_blocks_imported"],
        "handoff_fallbacks_clean": hst["handoff_fallbacks"],
        "tokens_recomputed_at_migration": (
            hst["tokens_recomputed_at_migration"]),
        "kill_failovers": kst["failovers"],
        "kill_handoff_fallbacks": kst["handoff_fallbacks"],
        "store_warm_blocks": wst["store_warm_blocks"],
        "warm_first_prefill_tokens": warm["probe"]["prefill_tokens"],
        "cold_first_prefill_tokens": cold["probe"]["prefill_tokens"],
        "store_quarantined": wst["store_quarantined"],
        "outputs_identical": True,  # hard-raised above
        "journal_dfa": "green --expect-closed incl. J011 (hard-raised)",
        # latency/throughput contrast (wall-clock; on-chip-pending)
        "ttft_cold_s": (round(cold["probe"]["ttft_s"], 4)
                        if cold["probe"]["ttft_s"] is not None else None),
        "ttft_warm_s": (round(warm["probe"]["ttft_s"], 4)
                        if warm["probe"]["ttft_s"] is not None else None),
        "tokens_per_sec_handoff": (
            round(handoff["tokens_per_sec"], 1)
            if handoff["tokens_per_sec"] else None),
        "n_requests": n_requests,
        "arrival": "poisson(rate=%g/step, seed=0), %d-token shared "
                   "header" % (rate, t_hdr),
        "knobs": {"max_slots": max_slots, "kv_block_tokens": bt,
                  "kv_store_bytes": 1 << 20, "handoff": True,
                  "kv_fingerprints": True},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_serving_frontdoor(dim=None, heads=None, layers_n=None,
                            vocab=None, max_len=None, max_slots=None,
                            n_replicas=2, n_warm=None, prompt_len=None,
                            max_new=None, sweep_duration_s=None,
                            rate_factors=(0.25, 0.5, 1.0, 2.5),
                            settle_s=30.0):
    """Wire-protocol front door acceptance (ISSUE 18): a 2-tenant
    open-loop load harness against the REAL serving surface — TCP
    sockets, NDJSON frames, auth -> tenant admission, token streaming
    — swept to the capacity knee, then kill- and disconnect-drilled.

      warm     one connection, blocking generates — compiles the
               engine, pins wire-vs-direct output identity (serving
               through the socket must not change what a request
               decodes to), and measures a capacity estimate (a
               saturating concurrent wave straight into the fleet)
               that anchors the sweep's rates
      sweep    fixed-seed Poisson arrivals at 0.25x/0.5x/1x/2.5x the
               estimated capacity, every request streamed; open loop,
               so past the knee the backlog grows without bound and
               the fleet's bounded admission sheds it as typed
               FLEET_SATURATED refusals — `find_knee` must locate a
               measurable knee (goodput flat vs offered + sheds/p99
               inflection), hard-raised if the sweep never saturates
      kill     the chaos variant: the same open-loop load at 0.5x
               capacity with a replica killed mid-load — >= 1
               failover, zero lost, zero duplicated, and every
               streamed request's chunks still concatenate
               bit-identically to its done frame (the journal-fed
               stream splice across failover), scored on the TTFT
               SLO histogram
      drop     a client opens a long streamed generate and vanishes:
               the fleet must journal a `cancelled` terminal and
               free the abandoned stream (disconnect == cancel)

    Hard raises: wire-vs-direct identity; at EVERY swept rate zero
    stream divergence, zero duplicated rids, zero unresolved requests
    (a deadline miss must surface as a typed shed, never silence —
    the well-behaved tenant's bar), zero sheds for the well-behaved
    tenant at the baseline rate; a located knee; kill-drill failover
    with lost == duplicate_refused == 0; >= 1 disconnect cancel; and
    the journal green through the DFA --expect-closed including the
    cancelled terminal and conn/stream side-bands. All timings are
    host wall-clock around socket I/O — CPU-honest shape columns
    (PERF.md), not chip throughput claims."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.diagnostics import format_diag
    from paddle_tpu.analysis.protocol_lint import verify_journal
    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.serving import (FrontDoor, ServingFleet,
                                    TenantRegistry, WireClient)
    from paddle_tpu.serving.loadgen import find_knee, run_open_loop

    cpu = jax.default_backend() == "cpu"
    if cpu:  # smoke shape: the knee is relative, the drills absolute
        dim, heads, layers_n = dim or 32, heads or 4, layers_n or 2
        vocab, max_len = vocab or 64, max_len or 128
        max_slots = max_slots or 4
        n_warm = n_warm or 6
        prompt_len, max_new = prompt_len or 6, max_new or 8
        sweep_duration_s = sweep_duration_s or 1.2
        dtype = jnp.float32
    else:
        dim, heads, layers_n = dim or 512, heads or 8, layers_n or 8
        vocab, max_len = vocab or 32000, max_len or 1024
        max_slots = max_slots or 8
        n_warm = n_warm or 8
        prompt_len, max_new = prompt_len or 24, max_new or 32
        sweep_duration_s = sweep_duration_s or 3.0
        dtype = jnp.bfloat16

    cfg = tlm.TransformerConfig(vocab=vocab, dim=dim, heads=heads,
                                layers=layers_n, max_len=max_len,
                                dtype=dtype)
    params = tlm.init_params(cfg, jax.random.PRNGKey(0))
    treg = TenantRegistry()
    # generous quotas: the knee must come from the fleet's bounded
    # admission (FLEET_SATURATED), not a token bucket — quota sheds
    # have their own bench (serving_multitenant)
    treg.add("alice", rate=1e6, burst=1e6, weight=3.0)
    treg.add("bob", rate=1e6, burst=1e6, weight=1.0)
    auth = {"tok-alice": "alice", "tok-bob": "bob"}
    tenants = [{"name": "alice", "token": "tok-alice", "weight": 3.0},
               {"name": "bob", "token": "tok-bob", "weight": 1.0}]

    keep_dir = os.environ.get("PADDLE_TPU_KEEP_JOURNAL_DIR") or None
    if keep_dir is not None:
        os.makedirs(keep_dir, exist_ok=True)
    jpath = tempfile.mktemp(suffix=".jsonl",
                            prefix="frontdoor_journal_", dir=keep_dir)
    fleet = ServingFleet(
        params, cfg, n_replicas=n_replicas, journal_path=jpath,
        heartbeat_timeout_s=300.0, monitor_interval_s=0.02,
        max_pending=1 << 16, tenants=treg,
        engine_kw={"max_slots": max_slots})
    fd = FrontDoor(fleet, auth=auth).start()
    rng = np.random.RandomState(0)
    try:
        # -- warm + wire-vs-direct identity ---------------------------
        warm_prompt = rng.randint(1, vocab, prompt_len).astype(np.int32)
        dh = fleet.submit(warm_prompt, max_new, seed=3, tenant="alice")
        dh.result(timeout=600)
        direct = [int(t) for t in dh.tokens]  # generated-only, like
        # the wire's done.tokens (result() prepends the prompt)
        wc = WireClient(fd.address, token="tok-alice")
        got = wc.generate_blocking("warm", warm_prompt, max_new, seed=3,
                                   stream=True)
        wc.close()
        if got["tokens"] != direct:
            raise RuntimeError(
                "wire answer diverges from the direct fleet answer "
                "for the same (prompt, seed): %r vs %r"
                % (got["tokens"], direct))
        if [t for c in got["chunks"] for t in c] != got["tokens"]:
            raise RuntimeError(
                "warm streamed chunks do not concatenate to the done "
                "frame: %r vs %r" % (got["chunks"], got["tokens"]))
        # capacity estimate: a saturating concurrent wave straight
        # into the fleet (full batching; the open-loop sweep cannot
        # exceed it, so rates anchored on it bracket the knee). The
        # FIRST wave pays the batch-shape compiles; only the second,
        # compile-warm wave is timed — an anchor deflated by compile
        # time would park the whole sweep under the knee
        for wave in range(2):
            hs = [fleet.submit(
                      rng.randint(1, vocab,
                                  prompt_len).astype(np.int32),
                      max_new, seed=100 + 10 * wave + i,
                      tenant="alice")
                  for i in range(n_warm)]
            t0 = time.time()
            for h in hs:
                h.result(timeout=600)
        cap_rps = n_warm / max(time.time() - t0, 1e-6)
        # size bounded admission so the top swept rate MUST shed: the
        # open-loop backlog past the knee overflows it by design
        fleet.max_pending = max(8, int(round(
            0.5 * cap_rps * sweep_duration_s)))

        # -- open-loop rate sweep to the knee -------------------------
        rates = [max(2.0, round(f * cap_rps, 2)) for f in rate_factors]
        reports = []
        for i, r in enumerate(rates):
            rep = run_open_loop(
                fd.address, tenants, r, sweep_duration_s, seed=7 + i,
                prompt_len=prompt_len, max_new_tokens=max_new,
                vocab=vocab, stream=True, settle_s=settle_s)
            if rep["stream_divergent"]:
                raise RuntimeError(
                    "rate %.2f rps: %d streamed request(s) diverged "
                    "from their done frame" % (r, rep["stream_divergent"]))
            if rep["duplicate_rids"]:
                raise RuntimeError(
                    "rate %.2f rps: %d duplicated rid(s) on the wire"
                    % (r, rep["duplicate_rids"]))
            if rep["wire_unresolved"]:
                raise RuntimeError(
                    "rate %.2f rps: %d request(s) got NO typed verdict "
                    "(lost on the wire — a deadline miss or shed must "
                    "be typed, never silent)"
                    % (r, rep["wire_unresolved"]))
            reports.append(rep)
        base = reports[0]["per_tenant"]["alice"]
        if base["shed"]:
            raise RuntimeError(
                "well-behaved tenant shed at the baseline rate "
                "(%.2fx capacity): %r"
                % (rate_factors[0], base["shed"]))
        knee = find_knee(reports)
        if knee["knee_rate_rps"] is None:
            raise RuntimeError(
                "rate sweep exhibited no measurable knee: %s"
                % knee["reason"])

        # -- kill drill: open-loop load + mid-load replica kill -------
        fleet.max_pending = 1 << 16   # the drill is about failover,
        failovers_before = fleet.stats()["failovers"]  # not shedding

        def chaos():
            with fleet._cond:
                holders = [i for i, m in enumerate(fleet._in_flight)
                           if m]
            fleet.kill_replica(holders[0] if holders else 0)

        kill_rep = run_open_loop(
            fd.address, tenants, max(2.0, round(0.5 * cap_rps, 2)),
            sweep_duration_s, seed=31, prompt_len=prompt_len,
            max_new_tokens=max_new, vocab=vocab, stream=True,
            deadline_s=float(settle_s), settle_s=settle_s,
            chaos_after_s=0.3 * sweep_duration_s, chaos_fn=chaos)
        st = fleet.stats()
        if st["failovers"] <= failovers_before:
            raise RuntimeError("kill drill produced no failover")
        if kill_rep["stream_divergent"]:
            raise RuntimeError(
                "kill drill: %d streamed request(s) diverged across "
                "failover" % kill_rep["stream_divergent"])
        if kill_rep["wire_unresolved"] or kill_rep["duplicate_rids"]:
            raise RuntimeError(
                "kill drill: %d unresolved, %d duplicated rid(s)"
                % (kill_rep["wire_unresolved"],
                   kill_rep["duplicate_rids"]))
        if kill_rep["per_tenant"]["alice"]["shed"].get(
                "DEADLINE_EXCEEDED"):
            raise RuntimeError(
                "kill drill: the well-behaved tenant missed its "
                "deadline %d time(s) under failover load"
                % kill_rep["per_tenant"]["alice"]["shed"]
                ["DEADLINE_EXCEEDED"])
        if not kill_rep["completed"]:
            raise RuntimeError("kill drill completed nothing")

        # -- disconnect drill: a streaming client vanishes ------------
        cancelled_before = fleet.stats()["cancelled"]
        for attempt in range(5):
            dc = WireClient(fd.address, token="tok-bob")
            dc.generate("drop-%d" % attempt,
                        rng.randint(1, vocab, prompt_len),
                        8 * max_new, seed=50 + attempt, stream=True)
            f = dc.recv()
            while f is not None and f.get("op") != "accepted":
                f = dc.recv()
            dc.close()
            t1 = time.time()
            while fleet.stats()["cancelled"] <= cancelled_before \
                    and time.time() - t1 < 10:
                time.sleep(0.01)
            if fleet.stats()["cancelled"] > cancelled_before:
                break
        st = fleet.stats()
        if st["cancelled"] <= cancelled_before:
            raise RuntimeError(
                "disconnect drill: no request was cancelled (the "
                "dropped connection's stream was never clawed back)")
        if st["lost"] or st["duplicate_refused"]:
            raise RuntimeError(
                "front door run lost/duplicated requests: %r"
                % {k: st[k] for k in ("lost", "duplicate_refused")})
        fd_stats = fd.stats()
        if not fd_stats["disconnect_cancels"]:
            raise RuntimeError(
                "fleet cancelled %d but the front door counted no "
                "disconnect cancel" % st["cancelled"])
    finally:
        fd.close()
        fleet.close()
    diags = verify_journal(jpath, expect_closed=True)
    if diags:
        raise RuntimeError(
            "journal DFA violations:\n  %s"
            % "\n  ".join(format_diag(d) for d in diags))
    if keep_dir is None:
        os.unlink(jpath)

    def row(rep):
        return {k: rep[k] for k in
                ("rate_rps", "offered_rps", "goodput_rps",
                 "ttft_p50_s", "ttft_p99_s", "ttft_p999_s",
                 "itl_p50_s", "itl_p99_s", "completed", "sent",
                 "shed")}

    return {
        # the sweep (host wall-clock; shape, not chip throughput)
        "capacity_est_rps": round(cap_rps, 2),
        "sweep": [row(r) for r in reports],
        "knee_rate_rps": knee["knee_rate_rps"],
        "knee_reason": knee["reason"],
        "baseline_shed_alice": 0,  # hard-raised above
        # the kill drill (SLO histogram carries the failover mass)
        "kill_drill": dict(row(kill_rep),
                           slo_histogram=kill_rep["slo_histogram"],
                           per_tenant=kill_rep["per_tenant"]),
        "kill_failovers": st["failovers"] - failovers_before,
        # exactly-once + disconnect accounting
        "requests_lost": st["lost"],
        "duplicates": st["duplicate_refused"],
        "cancelled": st["cancelled"],
        "cancel_late_refused": st["cancel_late_refused"],
        "disconnect_cancels": fd_stats["disconnect_cancels"],
        "stream_divergent": 0,      # hard-raised above, every phase
        "wire_vs_direct_identical": True,
        "journal_dfa": "green --expect-closed incl. cancelled + "
                       "conn/stream side-bands (hard-raised)",
        "frontdoor_stats": fd_stats,
        "knobs": {"n_replicas": n_replicas, "max_slots": max_slots,
                  "prompt_len": prompt_len, "max_new": max_new,
                  "sweep_duration_s": sweep_duration_s,
                  "rate_factors": list(rate_factors)},
        "model": {"dim": dim, "heads": heads, "layers": layers_n,
                  "vocab": vocab, "max_len": max_len},
    }


def bench_input_pipeline(n_shards=4, chunks_per_shard=8,
                         records_per_chunk=64, batch=64, step_s=0.004,
                         decode_sleep_s=0.0001, num_workers=2,
                         prefetch_batches=4):
    """Host-side input pipeline (paddle_tpu/data): the SAME fixed-seed
    synthetic shards + consumer, measured twice — prefetch OFF
    (num_workers=0: chunk decode runs synchronously inside next(), the
    pre-ISSUE-3 one-record-at-a-time posture) vs prefetch ON (decode
    threads + bounded queue overlap decode under the consumer's
    simulated step). The columns that matter are `wait_fraction` (share
    of consumer time blocked on input — the accelerator-idle fraction
    an input-bound job would see) and batches/s; both are pure host
    work, so the row is fully offline-measurable and deterministic in
    WHAT it delivers (the per-record checksum must match between runs —
    prefetch must never change what the model sees).

    `decode_sleep_s` adds a fixed GIL-RELEASING per-record decode cost
    on top of the small numpy work — the stand-in for real decodes
    (JPEG, decompression, tokenization in C) which release the GIL and
    therefore actually parallelize across the loader's threads. A
    decode that is pure small-ndarray Python stays GIL-bound and gains
    little from threads (CPython); the knob keeps the measured overlap
    about the pipeline, not about the GIL."""
    import pickle
    import tempfile

    from paddle_tpu.data import DataLoader, ShardedDataset, ShardWriter

    dim = 1024
    root = os.environ.get("BENCH_DATA_DIR") or tempfile.gettempdir()
    sdir = os.path.join(
        root, "bench_input_pipeline_%dx%dx%dx%d"
        % (n_shards, chunks_per_shard, records_per_chunk, dim))
    os.makedirs(sdir, exist_ok=True)
    paths = []
    for s in range(n_shards):
        p = os.path.join(sdir, "shard_%03d.rs" % s)
        paths.append(p)
        if os.path.exists(p):
            continue
        # per-shard RNG stream: skipping cached shards must not shift
        # the draws of the ones still to be written (a partially
        # populated cache dir would otherwise silently produce a
        # different "fixed-seed" trace than a fresh run)
        rng = np.random.RandomState(7 * 1000003 + s)
        rid = s * chunks_per_shard * records_per_chunk
        with ShardWriter(p, records_per_chunk=records_per_chunk) as w:
            for _ in range(chunks_per_shard * records_per_chunk):
                vec = rng.rand(dim).astype(np.float32)
                w.write(struct.pack("<I", rid) + vec.tobytes())
                rid += 1

    def decode(rec):
        (r,) = struct.unpack_from("<I", rec)
        vec = np.frombuffer(rec[4:], np.float32).astype(np.float64)
        vec = (vec - vec.mean()) / (vec.std() + 1e-6)  # host normalise
        if decode_sleep_s:
            time.sleep(decode_sleep_s)
        return r, vec.astype(np.float32)

    def run(workers, prefetch):
        import zlib

        ds = ShardedDataset(paths, decode_fn=decode, seed=7)
        dl = DataLoader(ds, batch, num_workers=workers,
                        prefetch_batches=prefetch)
        # ORDER-SENSITIVE digest (crc chained over ids in delivery
        # order): reordered batches or records must change it, or the
        # "prefetch never changes what the model sees" assert could not
        # catch a broken reassembly
        checksum = 0
        try:
            for ids, _vecs in dl:
                checksum = zlib.crc32(
                    np.ascontiguousarray(ids, np.int64).tobytes(),
                    checksum)
                time.sleep(step_s)  # the consumer's simulated step
        finally:
            dl.close()
        rep = dl.metrics.report()
        rep["checksum"] = checksum
        return rep

    off = run(0, 1)
    on = run(num_workers, prefetch_batches)
    assert on["checksum"] == off["checksum"], \
        "prefetch changed the delivered record stream"
    rec = {
        "prefetch_off": off,
        "prefetch_on": on,
        "wait_fraction_off": off["wait_fraction"],
        "wait_fraction_on": on["wait_fraction"],
        "batches_per_sec_off": off["batches_per_sec"],
        "batches_per_sec_on": on["batches_per_sec"],
        "overlap_speedup": round(off["wall_s"] / on["wall_s"], 3)
        if on["wall_s"] else None,
        "records": n_shards * chunks_per_shard * records_per_chunk,
        "batch": batch,
        "num_workers": num_workers,
        "prefetch_batches": prefetch_batches,
        "trace": "fixed-seed(7) synthetic shards, step_s=%g" % step_s,
    }
    return rec


def _make_sentinel_shards(sdir, n_shards, chunks_per_shard,
                          records_per_chunk, dim, seed, poison_chunk=None):
    """Fixed-seed linear-regression shards for the sentinel drills.
    Record = <I rid> ++ f64 features[dim] ++ f64 target. `poison_chunk`
    (a GLOBAL chunk index) gets its features scaled by 1e200 — the
    first batch touching it overflows the f64 loss to inf, the silent
    failure the sentinel must catch. Per-chunk RNG streams, so the
    poison never shifts any other chunk's draws."""
    from paddle_tpu.data import ShardWriter

    os.makedirs(sdir, exist_ok=True)
    w_true = np.linspace(-1.0, 1.0, dim)
    paths = []
    rid = 0
    for s in range(n_shards):
        p = os.path.join(sdir, "shard_%02d.rs" % s)
        paths.append(p)
        with ShardWriter(p, records_per_chunk=records_per_chunk) as w:
            for k in range(chunks_per_shard):
                gci = s * chunks_per_shard + k
                rng = np.random.RandomState(seed * 7919 + gci)
                for _ in range(records_per_chunk):
                    vec = rng.randn(dim)
                    y = float(vec @ w_true)
                    if gci == poison_chunk:
                        vec = vec * 1e200
                    w.write(struct.pack("<I", rid)
                            + vec.astype("<f8").tobytes()
                            + struct.pack("<d", y))
                    rid += 1
    return paths


class _CkptScope(dict):
    """Minimal scope (keys/get/set) for distributed.checkpoint."""

    def get(self, name):
        return dict.get(self, name)

    def set(self, name, value):
        self[name] = value


def _sentinel_training_job(ckpt_dir, shard_paths, quarantine_path, *,
                           dim=8, batch=16, epochs=2, lr=0.05, seed=11,
                           promote_after=4, ckpt_every=2,
                           rollback_budget=2, spike_factor=4.0,
                           hysteresis=1, warmup=2, injector=None,
                           max_incarnations=12):
    """Deterministic in-process stand-in for a supervised training
    worker: an incarnation loop (each pass = one worker lifetime) over
    resume_or_init -> train -> sentinel.observe -> checkpoint, where a
    sentinel trip ends the incarnation exactly like the subprocess
    worker's SENTINEL_EXIT_CODE exit would (tests/sentinel_worker.py
    is the real-process twin driven by the Supervisor). Pure float64
    numpy SGD on the fixed-seed shards — bit-deterministic, so loss
    curves can be compared EXACTLY across runs.

    Returns the full audit: committed loss curve (last write per step
    wins — a rollback's replay overwrites the diverged suffix), per-step
    batch ids, trips, per-incarnation resume records, and the final
    outcome ("done" / "abandon" / "incomplete")."""
    from paddle_tpu.data import DataLoader, ShardedDataset
    from paddle_tpu.distributed import checkpoint as ckpt_mod
    from paddle_tpu.distributed import sentinel as sent_mod

    rec_bytes = 4 + 8 * dim + 8

    def decode(rec):
        (rid,) = struct.unpack_from("<I", rec)
        vec = np.frombuffer(rec[4:4 + 8 * dim], "<f8")
        (y,) = struct.unpack_from("<d", rec, 4 + 8 * dim)
        assert len(rec) == rec_bytes
        return rid, np.asarray(vec), y

    curve = {}        # step -> loss (committed history, last write wins)
    step_ids = {}     # step -> batch record ids (same discipline)
    step_epoch = {}   # step -> loader epoch the batch came from
    trips = []
    resumes = []
    outcome = "incomplete"
    for inc in range(1, max_incarnations + 1):
        ds = ShardedDataset(shard_paths, decode_fn=decode, seed=seed,
                            quarantine_path=quarantine_path)
        dl = DataLoader(ds, batch, num_workers=0)
        detector = sent_mod.DivergenceDetector(
            spike_factor=spike_factor, hysteresis=hysteresis,
            warmup=warmup)
        sent = sent_mod.TrainingSentinel(
            ckpt_dir, quarantine_path=quarantine_path, dataset=ds,
            promote_after=promote_after, rollback_budget=rollback_budget,
            detector=detector)
        scope = _CkptScope()
        meta = ckpt_mod.resume_or_init(
            scope, ckpt_dir,
            stateful={"loader": dl, "detector": detector})
        if meta is not None:
            step = int(meta["extra"]["step"])
            w = np.asarray(scope.get("w"), np.float64)
            sent.align(step)
        else:
            step = 0
            w = np.zeros(dim, np.float64)
        resumes.append({
            "incarnation": inc,
            "step": None if meta is None else step,
            "known_good": sent.known_good_step,
            "fallbacks": [] if meta is None else meta.get("fallbacks", []),
        })
        status = None
        while dl.epoch < epochs and status is None:
            for ids, X, y in dl:
                if injector is not None:
                    injector.tick()
                step += 1
                # poisoned records overflow f64 BY DESIGN: the inf loss
                # is the signal under test, not a numerics accident
                with np.errstate(over="ignore", invalid="ignore"):
                    err = X @ w - y
                    loss = float(np.mean(err * err))
                if injector is not None:
                    loss = injector.poison_loss(loss)
                decision = sent.observe(step, loss,
                                        cursor=dl.state_dict())
                if decision is not None:
                    trips.append(decision)
                    status = decision["action"]
                    break
                w = w - lr * (2.0 / len(y)) * (X.T @ err)
                curve[step] = loss
                step_ids[step] = [int(r) for r in ids]
                step_epoch[step] = dl.epoch
                if step % ckpt_every == 0:
                    scope.set("w", w)
                    ckpt_mod.save_checkpoint(
                        scope, ckpt_dir, step=step,
                        extra={"step": step}, keep_last=2,
                        stateful={"loader": dl, "detector": detector},
                        protect=sent.known_good_step)
                    sent.on_checkpoint(step, cursor=dl.state_dict())
        dl.close()
        if status is None:
            outcome = "done"
            break
        if status == "abandon":
            outcome = "abandon"
            break
        # rollback / quarantine: the next incarnation resumes from the
        # known-good step (the diverged dirs were set aside by the trip)
    return {
        "outcome": outcome,
        "incarnations": inc,
        "trips": trips,
        "resumes": resumes,
        "curve": curve,
        "step_ids": step_ids,
        "step_epoch": step_epoch,
        "final_w": w.tolist(),
    }


def bench_training_sentinel(n_shards=2, chunks_per_shard=4,
                            records_per_chunk=32, batch=16, dim=8,
                            epochs=2, promote_after=4, ckpt_every=2,
                            rollback_budget=2, poison_pos=5, seed=11):
    """Silent-failure tolerance acceptance (ISSUE 10), pure host work.

    A fixed-seed supervised-training job whose deterministic chunk
    stream contains ONE poisoned chunk (1e200-scaled features -> inf
    loss the first batch that touches it). The sentinel must: trip,
    roll back to the last KNOWN-GOOD checkpoint (not the latest), trip
    again on the replay, quarantine the poison chunk (journaled exactly
    once), and complete with a finite loss curve IDENTICAL, step for
    step and bit for bit, to a clean-baseline run whose quarantine was
    pre-seeded with the same chunk — proving exact step/cursor
    continuity through two rollbacks and a quarantine. A separate
    sub-drill corrupts the newest checkpoint of a finished run and
    proves resume walks back to the newest verifiable step (bad dir
    renamed `.corrupt`, the failing CRC named) with zero manual
    intervention. Every invariant is asserted IN the bench, so the row
    cannot decay into a no-op."""
    import tempfile

    from paddle_tpu.data import ShardedDataset
    from paddle_tpu.distributed import checkpoint as ckpt_mod
    from paddle_tpu.distributed import fault_injection as fi
    from paddle_tpu.distributed import sentinel as sent_mod

    root = tempfile.mkdtemp(prefix="bench_sentinel_")
    # the poison chunk is chosen BY POSITION in epoch 0's deterministic
    # visitation order (so the trip step is stable), then written into
    # the shards at the matching global index
    probe_paths = _make_sentinel_shards(
        os.path.join(root, "probe"), n_shards, chunks_per_shard,
        records_per_chunk, dim, seed)
    order0 = ShardedDataset(probe_paths, seed=seed).epoch_order(0)
    poison_chunk = int(order0[poison_pos])

    kw = dict(dim=dim, batch=batch, epochs=epochs, seed=seed,
              promote_after=promote_after, ckpt_every=ckpt_every,
              rollback_budget=rollback_budget)

    # --- poisoned run: the sentinel earns its keep -------------------
    poisoned_paths = _make_sentinel_shards(
        os.path.join(root, "poisoned"), n_shards, chunks_per_shard,
        records_per_chunk, dim, seed, poison_chunk=poison_chunk)
    qpath = os.path.join(root, "poisoned", "quarantine.jsonl")
    job = _sentinel_training_job(
        os.path.join(root, "poisoned", "ckpt"), poisoned_paths, qpath,
        **kw)
    assert job["outcome"] == "done", job["outcome"]
    assert len(job["trips"]) >= 1, "sentinel never tripped"
    # every rollback landed on the known-good step of its trip, and the
    # next incarnation resumed EXACTLY there
    for i, trip in enumerate(job["trips"]):
        resume = job["resumes"][i + 1]
        assert resume["step"] == trip["rollback_to"], (trip, resume)
    # the poison chunk is journaled exactly once, with the right blame
    q_entries = [e for e in sent_mod.quarantine_entries(qpath)
                 if e["chunk"] == poison_chunk]
    assert len(q_entries) == 1, q_entries
    quarantined = sorted(sent_mod.quarantined_chunks(qpath))
    # attribution is exact on this trace: the hard trip fires on the
    # first poisoned batch, so the healthy-cursor window names the
    # poison chunk ALONE — no clean chunk loses its data
    assert quarantined == [poison_chunk], quarantined
    curve = job["curve"]
    losses = [curve[s] for s in sorted(curve)]
    assert np.isfinite(losses).all(), "non-finite loss in committed curve"

    # --- clean baseline: same job, quarantine pre-seeded -------------
    clean_paths = _make_sentinel_shards(
        os.path.join(root, "clean"), n_shards, chunks_per_shard,
        records_per_chunk, dim, seed)
    q_clean = os.path.join(root, "clean", "quarantine.jsonl")
    sent_mod.quarantine_chunks(q_clean, quarantined,
                               reason="clean-baseline preseed")
    clean = _sentinel_training_job(
        os.path.join(root, "clean", "ckpt"), clean_paths, q_clean, **kw)
    assert clean["outcome"] == "done" and not clean["trips"], clean["trips"]
    assert sorted(curve) == sorted(clean["curve"]), "step sets differ"
    curve_matches = all(curve[s] == clean["curve"][s] for s in curve)
    assert curve_matches, "post-quarantine curve diverged from clean run"
    ids_match = all(job["step_ids"][s] == clean["step_ids"][s]
                    for s in curve)
    assert ids_match, "delivered record stream diverged from clean run"
    # no record double-delivered or skipped in the committed stream:
    # per epoch, every non-quarantined record id appears exactly once
    n_rec = n_shards * chunks_per_shard * records_per_chunk
    quarantined_ids = set()
    for c in quarantined:
        quarantined_ids |= set(range(c * records_per_chunk,
                                     (c + 1) * records_per_chunk))
    for epoch in range(epochs):
        ids = [r for s in curve if job["step_epoch"][s] == epoch
               for r in job["step_ids"][s]]
        assert len(ids) == len(set(ids)), "double-delivered records"
        assert set(ids) == set(range(n_rec)) - quarantined_ids

    # --- corrupted-latest resume: zero manual intervention -----------
    clean_ckpt = os.path.join(root, "clean", "ckpt")
    steps_before = ckpt_mod.retain(clean_ckpt, keep_last=10)
    newest = steps_before[0]
    npy = sorted(glob.glob(os.path.join(
        clean_ckpt, "step_%010d" % newest, "*.npy")))[0]
    fi.corrupt_file(npy)
    resumed = _sentinel_training_job(clean_ckpt, clean_paths, q_clean,
                                     **kw)
    assert resumed["outcome"] == "done"
    fallbacks = resumed["resumes"][0]["fallbacks"]
    assert fallbacks and fallbacks[0]["step"] == newest, fallbacks
    assert any("CRC" in p for p in fallbacks[0]["problems"]), fallbacks
    assert os.path.isdir(fallbacks[0]["renamed_to"])
    assert resumed["resumes"][0]["step"] == steps_before[1]

    return {
        "sentinel_trips": len(job["trips"]),
        "trip_verdicts": [t["verdict"] for t in job["trips"]],
        "rollback_to": [t["rollback_to"] for t in job["trips"]],
        "rollbacks_landed_on_known_good": True,
        "incarnations": job["incarnations"],
        "poison_chunk": poison_chunk,
        "quarantined_chunks": quarantined,
        "poison_journaled_once": True,
        "final_loss": losses[-1],
        "steps_total": len(curve),
        "curve_finite": True,
        "curve_matches_clean": curve_matches,
        "record_stream_matches_clean": ids_match,
        "corrupt_resume": {
            "ok": True,
            "corrupted_step": newest,
            "walked_back_to": resumed["resumes"][0]["step"],
            "renamed_to": os.path.basename(fallbacks[0]["renamed_to"]),
            "problem": fallbacks[0]["problems"][0],
        },
        "knobs": {"promote_after": promote_after,
                  "ckpt_every": ckpt_every,
                  "rollback_budget": rollback_budget},
        "trace": "fixed-seed(%d) shards, poison at epoch0 pos %d"
                 % (seed, poison_pos),
    }


def bench_flash_attention(B=4, T=4096, H=16, D=64, steps=(4, 16)):
    """Pallas flash attention vs XLA full-matrix attention, single chip,
    bf16, causal (parallel/flash_attention.py). Timing puts the
    iterations inside one lax.scan and differences two step counts,
    which cancels the per-call dispatch and sync cost."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.parallel import flash_attention, reference_attention

    if jax.default_backend() == "cpu":
        return {"skipped": "pallas flash timing needs the TPU backend "
                           "(CPU runs it in interpret mode only)"}

    rng = np.random.RandomState(0)
    base = rng.randn(B, T, H, D).astype(np.float32) * 0.1
    q = jnp.asarray(base + 1e-3, jnp.bfloat16)
    k = jnp.asarray(base, jnp.bfloat16)
    v = jnp.asarray(base * 0.5, jnp.bfloat16)

    def per_iter(attn):
        def multi(n):
            @jax.jit
            def f(q, k, v):
                def body(c, _):
                    o = attn(c, k, v)
                    # feed the output back so no iteration is dead code
                    return (c + 1e-6 * o).astype(c.dtype), ()

                out, _ = lax.scan(body, q, None, length=n)
                return out.sum()

            return f

        # scalar readback forces completion
        run_at = _jit_per_count(multi, lambda f: float(f(q, k, v)))
        return _diff_time(run_at, *steps, return_info=True)

    def per_iter_grad(attn):
        """fwd+bwd per-iteration cost: grads chain into the carry so no
        iteration is dead code (r5: exercises the pallas backward)."""
        def loss(c, kk, vv):
            return attn(c, kk, vv).astype(jnp.float32).sum()

        def multi(n):
            @jax.jit
            def f(q, k, v):
                def body(c, _):
                    gq = jax.grad(loss)(c, k, v)
                    return (c + 1e-6 * gq).astype(c.dtype), ()

                out, _ = lax.scan(body, q, None, length=n)
                return out.sum()

            return f

        run_at = _jit_per_count(multi, lambda f: float(f(q, k, v)))
        return _diff_time(run_at, *steps, return_info=True)

    dt_flash, t_flash = per_iter(
        lambda c, kk, vv: flash_attention(c, kk, vv, causal=True))
    dt_ref, t_ref = per_iter(
        lambda c, kk, vv: reference_attention(c, kk, vv, causal=True))
    dt_fb_flash, t_fb_flash = per_iter_grad(
        lambda c, kk, vv: flash_attention(c, kk, vv, causal=True))
    dt_fb_ref, t_fb_ref = per_iter_grad(
        lambda c, kk, vv: reference_attention(c, kk, vv, causal=True))
    ms_flash, ms_ref = dt_flash * 1e3, dt_ref * 1e3
    err = float(jnp.abs(
        flash_attention(q, k, v, causal=True).astype(jnp.float32)
        - reference_attention(q, k, v, causal=True).astype(jnp.float32)
    ).max())
    # causal attention fwd FLOPs: 2 matmuls, half the T^2 window
    flops = 2.0 * B * H * T * T * D
    return {
        "ms_flash": round(ms_flash, 3),
        "ms_xla_full": round(ms_ref, 3),
        "speedup": round(ms_ref / ms_flash, 3),
        "flash_tflops": round(flops / (ms_flash / 1e3) / 1e12, 1),
        # fwd+bwd: the pallas backward (two tiled passes off the lse
        # residual) vs XLA autodiff of the full-matrix attention
        "ms_fwdbwd_flash": round(dt_fb_flash * 1e3, 3),
        "ms_fwdbwd_xla": round(dt_fb_ref * 1e3, 3),
        "fwdbwd_speedup": round(dt_fb_ref / dt_fb_flash, 3),
        "max_err": err,
        "dtype": "bfloat16",
        "shape": [B, T, H, D],
        "timing": {"flash": t_flash, "xla_full": t_ref,
                   "fwdbwd_flash": t_fb_flash, "fwdbwd_xla": t_fb_ref},
    }


def main():
    os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "bfloat16")

    # bounded device-init wait: a backend that never answers otherwise
    # hangs the bench forever inside jax.devices() with no output at
    # all. The watchdog turns that into a diagnostic line + clean
    # nonzero exit the driver can act on.
    import threading

    _state = {"headline": None, "workloads": {}}

    init_timeout = float(os.environ.get("BENCH_INIT_TIMEOUT_S", "1200"))
    total_timeout = float(os.environ.get("BENCH_TOTAL_TIMEOUT_S", "7200"))
    global _DEADLINE
    _DEADLINE = time.monotonic() + total_timeout
    init_done = threading.Event()

    def _watchdog():
        start = time.monotonic()
        if not init_done.wait(init_timeout):
            print(json.dumps({
                "metric": "bench_error",
                "error": "device init exceeded %gs — accelerator "
                         "backend unavailable" % init_timeout,
            }), flush=True)
            os._exit(3)
        # stay armed for the WHOLE run: a device call that never
        # returns otherwise blocks with no output at all.
        # Budget from ACTUAL elapsed init time (a fast init must not
        # shrink the run budget; a total <= init_timeout must still arm)
        remaining = total_timeout - (time.monotonic() - start)
        if remaining <= 0:
            # init alone consumed the whole budget: report rather than
            # silently disarming mid-run coverage
            print(
                json.dumps({
                    "metric": "bench_error",
                    "error": "device init consumed the whole "
                             "BENCH_TOTAL_TIMEOUT_S=%g budget"
                             % total_timeout,
                }),
                flush=True,
            )
            os._exit(3)
        if not _bench_finished.wait(remaining):
            # the headline runs FIRST: if a later side workload hung,
            # mark the hang (not silent) and still emit the contract
            # line before exiting
            if _state.get("headline") is not None:
                _state["workloads"]["bench_watchdog"] = {
                    "error": "side workload hung past "
                             "BENCH_TOTAL_TIMEOUT_S=%g; headline was "
                             "already measured" % total_timeout,
                }
                _emit_headline()
                os._exit(0)
            print(
                json.dumps({
                    "metric": "bench_error",
                    "error": "bench exceeded BENCH_TOTAL_TIMEOUT_S=%g — "
                             "device call likely hung mid-run"
                             % total_timeout,
                }),
                flush=True,
            )
            os._exit(3)

    _bench_finished = threading.Event()
    threading.Thread(target=_watchdog, daemon=True).start()

    def _emit_headline():
        """The driver-contract line (LAST line printed). Called on the
        normal path and by the watchdog if a side workload hangs after
        the headline was already measured."""
        headline = _state.get("headline")
        if headline is None:
            return False
        print(
            json.dumps(
                {
                    "metric": "resnet50_train_images_per_sec_per_chip",
                    "value": headline["img_per_sec"],
                    "unit": "images/sec",
                    "vs_baseline": round(
                        headline["img_per_sec"] / BASELINE_IMG_PER_SEC, 4
                    ),
                    "mfu": headline["mfu"],
                    # measurement audit trail: raw chunk timings +
                    # spread; stable == spread <= BENCH_SPREAD_LIMIT on
                    # both step counts (r3 verdict falsifiability ask)
                    "stable": headline.get("timing", {}).get("stable"),
                    "timing": headline.get("timing"),
                    "workloads": _state["workloads"],
                }
            ),
            flush=True,
        )
        return True

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    jax.config.update(
        "jax_default_matmul_precision",
        os.environ["JAX_DEFAULT_MATMUL_PRECISION"],
    )
    enable_compile_cache()

    def _init_failed(why):
        print(json.dumps({"metric": "bench_error", "error": why}),
              flush=True)
        sys.exit(3)

    # the watchdog covers an init that HANGS; one that raises, or comes
    # up without a TPU, is one error line and a nonzero exit right away
    # — this file measures the chip and has no CPU mode
    try:
        dev = jax.devices()[0]  # backend init, under the watchdog
    except Exception as e:  # whatever the backend raises at start-up
        _init_failed("device init raised %s: %s" % (type(e).__name__, e))
    if dev.platform != "tpu":
        _init_failed("no TPU: JAX came up on %s (%s)"
                     % (dev.platform, dev.device_kind))
    init_done.set()
    from paddle_tpu.models.alexnet import alexnet
    from paddle_tpu.models.googlenet import googlenet
    from paddle_tpu.models.mobilenet import mobilenet_v1
    from paddle_tpu.models.resnet import resnet_imagenet
    from paddle_tpu.models.vgg import vgg16

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    # BENCH_STEPS="lo,hi" overrides the headline's two step counts (CPU
    # smoke tests use tiny counts; the TPU default stays 12,72)
    steps = tuple(
        int(s) for s in os.environ.get("BENCH_STEPS", "12,72").split(",")
    )

    quick = os.environ.get("BENCH_QUICK", "0") == "1"
    only = os.environ.get("BENCH_ONLY", "").split(",") if os.environ.get("BENCH_ONLY") else None
    # wall-clock budget for the SIDE workloads: the driver must still
    # get the headline line on a slow day, so once the budget is spent
    # remaining side workloads are skipped (marked, not silent). 3600
    # leaves room for the chunk-scaled workloads (probe chunks + two
    # extra compiles each) and the lm_large/lm_xl rows; worst case
    # headline (~300 s) + sides (3600 s) stays under the 7200 s watchdog
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "3600"))
    workloads = _state["workloads"]

    def run(name, fn):
        """Side workloads only — the resnet50 headline runs outside run()
        so its failure fails the bench instead of being swallowed."""
        if only and name not in only:
            return
        if time.time() - t_start > budget_s:
            workloads[name] = {"skipped": "side-workload budget exhausted "
                                          "(BENCH_BUDGET_S=%g)" % budget_s}
        else:
            try:
                workloads[name] = fn()
            except Exception as e:  # a broken side workload must not kill the headline
                workloads[name] = {"error": "%s: %s" % (type(e).__name__, e)}
        rec = dict(workloads[name])
        rec["metric"] = name
        print(json.dumps(rec), flush=True)

    # headline FIRST (chip training throughput; device-resident data,
    # per-step cost by multi-step differencing): a slow day must not
    # starve the driver-contract number behind the side workloads. The
    # line still prints LAST (or from the watchdog on a hang).
    _state["headline"] = bench_image(
        "resnet50",
        lambda i, c: resnet_imagenet(i, class_dim=c, depth=50),
        batch,
        steps=steps,
        xla_cost=True,
    )
    workloads["resnet50"] = _state["headline"]
    # the side budget starts AFTER the headline: it belongs to the side
    # workloads alone
    t_start = time.time()

    # reference GPU baselines in img/s: AlexNet 334 ms/batch bs=128,
    # GoogLeNet 1149 ms/batch bs=128 (benchmark/README.md:37,50); no GPU
    # number exists in-tree for VGG16
    if not quick:
        run("alexnet", lambda: bench_image(
            "alexnet", lambda i, c: alexnet(i, c), 128, baseline_ips=383.2))
        run("googlenet", lambda: bench_image(
            "googlenet", lambda i, c: googlenet(i, c), 128, baseline_ips=111.4))
        run("vgg16", lambda: bench_image("vgg16", lambda i, c: vgg16(i, c), 64))
        run("mobilenet", lambda: bench_image(
            "mobilenet", lambda i, c: mobilenet_v1(i, c), 128))
        # the memory_optimize pass on the headline model: recompute
        # trades HBM residency for FLOPs — records the throughput cost
        run("resnet50_remat", lambda: bench_image(
            "resnet50", lambda i, c: resnet_imagenet(
                i, class_dim=c, depth=50), batch, remat=True))
        # serving-side: the reference's only published inference numbers
        # are the CPU MKL-DNN bs=16 table (IntelOptimizedPaddle.md:77-107)
        run("resnet50_infer", lambda: bench_image_infer(
            "resnet50",
            lambda i, c: resnet_imagenet(i, class_dim=c, depth=50),
            217.69))
        if os.environ.get("BENCH_INFER_ALL") == "1":
            # the rest of the reference inference table, opt-in to keep
            # the driver's side budget bounded. The reference's VGG row
            # is VGG-19 (IntelOptimizedPaddle.md:29,71), so the infer
            # bench runs the true vgg19 model against it.
            from paddle_tpu.models.vgg import vgg19

            run("vgg19_infer", lambda: bench_image_infer(
                "vgg19", lambda i, c: vgg19(i, c), 96.75))
            run("googlenet_infer", lambda: bench_image_infer(
                "googlenet", lambda i, c: googlenet(i, c), 600.94))
            run("alexnet_infer", lambda: bench_image_infer(
                "alexnet", lambda i, c: alexnet(i, c), 850.51))
        run("profiler_reconciliation", bench_profiler_reconciliation)
        run("lstm", bench_lstm)
        run("sparse_embedding", bench_sparse_embedding)
        run("flash_attention", bench_flash_attention)
        run("lm_decode", bench_lm_decode)
        # continuous-batching serving engine: many concurrent requests
        # through one compiled decode step (ISSUE 2); deterministic
        # Poisson trace — occupancy/compile counts meaningful offline,
        # tokens/s only on the chip
        run("serving_decode", bench_serving_decode)
        # prefix-cache acceptance: the SAME fixed-seed shared-header
        # trace with the pool off vs on — prefill-tokens-computed and
        # hit rate are deterministic offline, TTFT deltas on-chip
        run("serving_shared_prefix", bench_serving_shared_prefix)
        # paged KV block pool + speculative decoding (ISSUE 7): one
        # fixed KV budget, slab vs paged vs paged+spec — peak resident
        # slots, accept-rate, and output identity are deterministic
        # offline; the tokens/s contrast only on the chip
        run("serving_paged", bench_serving_paged)
        # fused paged-attention kernel (ISSUE 13): the same fixed-seed
        # shared-header trace gather vs fused — output identity, zero
        # _paged_view gathers, and the one-compiled-step discipline
        # are deterministic offline; the tokens/s contrast is only
        # meaningful compiled to Mosaic on-chip
        run("serving_paged_kernel", bench_serving_paged_kernel)
        # quantized serving (ISSUE 14): one fixed KV byte budget,
        # kv_quant none/int8/fp8 + weight-int8 — slots-resident,
        # bytes-per-resident-token, and the greedy-agreement quality
        # gate are deterministic offline; the tokens/s contrast (the
        # HBM-roofline win) only on the chip
        run("serving_quant", bench_serving_quant)
        # serving fleet (ISSUE 6): N replicas + kill drill on the same
        # fixed-seed shared-header trace — requests lost / duplicates /
        # failovers and the affinity-routing reuse contrast are
        # deterministic offline; tokens/s and speedup-vs-N×1 on-chip
        run("serving_fleet", bench_serving_fleet)
        # request-SLO / gray-failure drill (ISSUE 8): deadlines + one
        # replica gray-slowed mid-trace — expired (must be 0), demote/
        # probe/restore counts, journal-verified re-decode-zero resume,
        # and the p99 TTFT tail bound are deterministic offline
        run("serving_slo", bench_serving_slo)
        # disaggregated elastic fleet (ISSUE 11): the same burst trace
        # static vs elastic (tiers + autoscaler + one mid-trace weight
        # rollout + corrupted-candidate abort drill) — spawn/retire/
        # migration/rollout counts, the J009 version-fence audit, and
        # output identity are deterministic offline
        run("serving_elastic", bench_serving_elastic)
        # multi-tenant serving (ISSUE 12): tenant quotas + weighted
        # fair queueing + paged LoRA adapters + the zoo batch lane —
        # quota/fairness/adapter-paging/output-identity columns are
        # deterministic offline; per-tenant tok/s on-chip
        run("serving_multitenant", bench_serving_multitenant)
        # serving integrity (ISSUE 15): garble@ + flip@ silent-fault
        # drills — trip/quarantine exactly-once, output identity to
        # the uninjected run, and the J010 taint-fence audit are
        # deterministic offline; the overhead tokens/s column on-chip
        run("serving_integrity", bench_serving_integrity)
        # durable KV (ISSUE 16): checksummed block handoff at migration
        # + the crash-survivable tiered store — zero-recompute clean
        # handoff, counted kill-drill fallback, store-warmed restart,
        # output identity, and the J011 handoff-fence audit are
        # deterministic offline; the warm/cold TTFT contrast on-chip
        run("serving_kv_handoff", bench_serving_kv_handoff)
        # wire front door (ISSUE 18): open-loop Poisson load over real
        # sockets swept to the capacity knee + kill/disconnect drills —
        # stream bit-identity, typed sheds, exactly-once, and the
        # cancelled-terminal DFA audit are deterministic offline; every
        # timing is host wall-clock (CPU-honest shape, PERF.md)
        run("serving_frontdoor", bench_serving_frontdoor)
        run("transformer_lm", bench_transformer_lm)
        # larger-matmul flagship: dim=1024 keeps every matmul MXU-shaped
        # (the dim=512 row leaves lane headroom), so this is the MFU
        # headline for the LM family; beyond-reference, no 2018 baseline
        run("transformer_lm_large", lambda: bench_transformer_lm(
            B=8, T=2048, dim=1024, heads=16, layers_n=12))
        # dim=2048: the widest matmuls of the LM family
        run("transformer_lm_xl", lambda: bench_transformer_lm(
            B=2, T=2048, dim=2048, heads=16, layers_n=16, steps=(2, 8)))

    # the default batch stays at the historically comparable 128
    chunk_steps = int(os.environ.get("BENCH_CHUNK_STEPS", "25"))
    n_chunks = int(os.environ.get("BENCH_CHUNKS", "6"))

    # end-to-end input pipeline (recordio -> host decode -> h2d -> train)
    if not quick:
        # the pure-host loader-overlap row first (paddle_tpu/data): no
        # device work at all, so it is meaningful on every backend
        run("input_pipeline", bench_input_pipeline)
        # training sentinel (ISSUE 10): poisoned-chunk divergence ->
        # rollback-to-known-good -> quarantine -> finite curve identical
        # to the clean baseline, plus the corrupted-latest resume drill
        # — pure host work, deterministic on every backend
        run("training_sentinel", bench_training_sentinel)
        run("resnet50_input_pipeline",
            lambda: bench_resnet50_recordio(batch, chunk_steps, n_chunks))

    _bench_finished.set()
    _emit_headline()


if __name__ == "__main__":
    main()
