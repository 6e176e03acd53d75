#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one TPU chip: trainer, CLI, serving
    python chip_smoke.py --chips 4   four chips: ONLY the multi-chip paths
                                     and what they are compared with
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny [--chips 4]
                                     the same control flow at toy shapes,
                                     kernels interpreted — a rehearsal,
                                     whose last line says "cpu" and can
                                     never pass for the chip

One process (a chip belongs to one process at a time; nothing here
starts a child), the entry points a user calls, weights and data from
`--seed`. Every phase checks its own results and the run fails if any
phase failed. Everything worth knowing is printed one JSON object per
line; on success the LAST line is

    {"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}

with the device as JAX reports it. No other outcome prints an "ok"
line, and the exit code is then not 0. Times printed here are smoke
observations (one run, host clock around blocked calls, compile time
reported apart), not benchmark results.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
import traceback

import numpy as np


class SmokeFailure(AssertionError):
    """A phase's result is wrong."""


def emit(**rec):
    print(json.dumps(rec), flush=True)


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def _on(array, devices) -> bool:
    return set(array.devices()) <= set(devices)


def _peak_bytes(dev):
    stats = dev.memory_stats()  # None on the CPU backend
    return None if not stats else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------
# train: ResNet-50 through fluid.Executor, then the legacy trainer CLI
# ---------------------------------------------------------------------

def _resnet_program(fluid, batch, hw, classes, depth):
    from paddle_tpu.models.resnet import resnet_imagenet

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        image = fluid.layers.data(name="image", shape=[3, hw, hw],
                                  dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        predict = resnet_imagenet(image, class_dim=classes, depth=depth)
        loss = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=predict, label=label))
        fluid.optimizer.Momentum(learning_rate=0.01,
                                 momentum=0.9).minimize(loss)
    main.amp = True  # bf16 compute, f32 master weights
    return main, startup, loss


def _resnet_shape(tiny):
    # batch, image side, classes, depth
    return (8, 32, 10, 18) if tiny else (128, 224, 1000, 50)


def _resnet_feed(seed, batch, hw, classes):
    rng = np.random.RandomState(seed)
    return {"image": rng.rand(batch, 3, hw, hw).astype(np.float32),
            "label": rng.randint(0, classes, (batch, 1)).astype(np.int32)}


def _train_steps(exe, main, loss, feed, steps):
    """`steps` executor steps on one fixed batch -> (device losses,
    first-step seconds — compile included — and the later steps')."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.monotonic()
        (out,) = exe.run(main, feed=feed, fetch_list=[loss],
                         return_numpy=False)
        out.block_until_ready()
        secs.append(time.monotonic() - t0)
        losses.append(out)
    return losses, secs


def phase_train(args, devices):
    import jax

    import paddle_tpu.fluid as fluid

    batch, hw, classes, depth = _resnet_shape(args.tiny)
    steps = 8
    main, startup, loss = _resnet_program(fluid, batch, hw, classes, depth)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feed = {k: jax.device_put(v) for k, v in
                _resnet_feed(args.seed, batch, hw, classes).items()}
        losses, secs = _train_steps(exe, main, loss, feed, steps)
        stats = exe.cache_stats()
        off_device = [n for n in scope.keys()
                      if not _on(scope.get(n), devices[:1])]
        n_vars = len(scope.keys())
    vals = [float(np.ravel(np.asarray(x))[0]) for x in losses]
    emit(phase="train", model="resnet%d" % depth, batch=batch,
         image=[3, hw, hw], amp="bfloat16", optimizer="momentum",
         losses=[round(v, 4) for v in vals],
         compile_and_first_step_s=round(secs[0], 2),
         step_s_median=float(np.median(secs[1:])),
         executor_cache=stats, n_scope_vars=n_vars,
         peak_bytes_in_use=_peak_bytes(devices[0]))
    check(all(np.isfinite(vals)), "non-finite training loss: %r" % vals)
    check(vals[-1] < vals[0],
          "loss did not fall on a fixed batch: %r" % vals)
    # startup + main are the only two programs this executor has seen:
    # one compile each, every later step a cache hit
    check(stats["misses"] == 2 and stats["hits"] == steps - 1,
          "main program compiled more than once: %r" % stats)
    check(not off_device and _on(losses[-1], devices[:1]),
          "values off %s: %s" % (devices[0], off_device[:5]))


def phase_cli(args, devices):
    """The legacy `python -m paddle_tpu.trainer` entry, in-process."""
    import os

    from paddle_tpu import trainer

    here = os.path.dirname(os.path.abspath(__file__))
    config = ("smallnet_mnist_cifar.py" if args.tiny else "resnet.py")
    batch, n = (16, 8) if args.tiny else (64, 10)
    t0 = time.monotonic()
    stats = trainer.main([
        "--job=time",
        "--config=" + os.path.join(here, "benchmarks/paddle/image", config),
        "--config_args=batch_size=%d,num_samples=%d" % (batch, batch * n),
        "--log_period=5",
    ])
    emit(phase="cli", config=config, batch=batch,
         batches=stats["batches"], first_cost=stats.get("first_cost"),
         cost=stats["cost"], ms_per_batch=stats["ms_per_batch"],
         total_s=round(time.monotonic() - t0, 2),
         peak_bytes_in_use=_peak_bytes(devices[0]))
    check(stats["batches"] == n, "CLI ran %r batches, not %d"
          % (stats["batches"], n))
    check(np.isfinite(stats["cost"]), "CLI cost %r" % stats["cost"])
    check(stats["ms_per_batch"] is not None, "CLI timed no batch")


# ---------------------------------------------------------------------
# serve: the 16x2048 transformer behind ServingEngine, fleet, front door
# ---------------------------------------------------------------------

def _lm_config(tiny, dtype):
    from paddle_tpu.models import transformer as tlm

    if tiny:
        return tlm.TransformerConfig(vocab=64, dim=64, heads=4, layers=1,
                                     max_len=32, dtype=dtype)
    return tlm.TransformerConfig(vocab=32000, dim=2048, heads=16,
                                 layers=16, max_len=2048, dtype=dtype)


def _prompts(rng, cfg, min_bucket=8):
    """One prompt per prefill bucket the engine can emit: lengths
    strictly inside (bucket/2, bucket], the largest leaving room for
    the generated tokens under max_len."""
    out, b = [], min_bucket
    while b <= cfg.max_len:
        n = min(b - b // 4, cfg.max_len - 10)
        out.append(rng.randint(0, cfg.vocab, n).astype(np.int32))
        b *= 2
    return out


def _reference_logits(params, cfg, seq):
    """The plain reference: f32 weights, full-matrix attention, every
    matmul at the highest precision -> logits [T, vocab]."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm

    with jax.default_matmul_precision("highest"):
        p32 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        out = jax.jit(lambda p, t: tlm.forward(
            p, t, cfg, attn_impl="reference"))(p32, jnp.asarray(seq)[None])
        return np.asarray(out[0], np.float32)


def _kernel_logits(params, cfg, prompt, block_tokens):
    """The two primitives the engine's compiled steps are made of, on a
    pool of their own: one padded prefill chunk through the fused
    kernel, then one decode step of the greedy next token ->
    (prefill logits [V], that token, decode logits [V])."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid.core.kernels_sequence import bucket_pow2
    from paddle_tpu.models import transformer as tlm

    n = len(prompt)
    Cb = bucket_pow2(n, floor=8)
    maxb = -(-(Cb + 1) // block_tokens)
    cache = tlm.init_paged_kv_cache(cfg, maxb + 1, block_tokens)
    table = jnp.arange(maxb, dtype=jnp.int32)
    padded = np.zeros(Cb, np.int32)
    padded[:n] = prompt
    lg0, cache = jax.jit(
        lambda p, c, x: tlm.paged_prefill_chunk(
            p, c, x, jnp.int32(0), table, cfg, true_len=jnp.int32(n),
            kernel="fused"))(params, cache, jnp.asarray(padded))
    nxt = int(jnp.argmax(lg0))
    lg1, _ = jax.jit(
        lambda p, c, t: tlm.paged_decode_step(
            p, t, jnp.full((1,), n, jnp.int32), table[None], c, cfg,
            kernel="fused"))(params, cache, jnp.asarray([nxt], jnp.int32))
    return (np.asarray(lg0, np.float32), nxt,
            np.asarray(lg1[0], np.float32))


def _drive_engine(params, cfg, requests, check_text, **engine_kw):
    """Build one engine, serve `requests` [(prompt, max_new, temperature,
    seed)] to completion, return the tokens, the finish reasons and the
    facts worth printing (no engine or handle outlives this call, so
    its pool is freed on return)."""
    from paddle_tpu.serving import ServingEngine

    t0 = time.monotonic()
    eng = ServingEngine(params, cfg, **engine_kw)
    hs = [eng.submit(p, n, temperature=t, seed=s)
          for p, n, t, s in requests]
    eng.run()
    wall = time.monotonic() - t0
    rep = eng.metrics.report()
    # host clock by row of the engine's phases (`ServingMetrics.phase`):
    # `decode_step` / `prefill_T<bucket>` are whole decode and chunk
    # phases, `engine.dispatch` / `engine.device_wait` / ... their
    # parts; a row's slowest call is the one that compiled
    step_s = {
        name: {"calls": n, "with_compile": round(worst, 3),
               "min": round(best, 5),
               "mean_of_rest": (round((total - worst) / (n - 1), 5)
                                if n > 1 else None)}
        for name, (n, total, best, worst)
        in sorted(eng.metrics.ops.rows.items())}
    facts = {
        "decode_traces": eng.metrics.decode_trace_count(),
        "prefill_traces": sorted(
            int(k.split("_T")[1]) for k in eng.metrics.trace_counts
            if k.startswith("prefill_T")),
        "decode_steps": rep["decode_steps"],
        "paged_kernel": eng.paged_kernel, "kv_quant": eng.kv_quant,
        "kv_block_tokens": eng.kv_block_tokens,
        "kv_pool_blocks": eng.num_kv_blocks, "wall_s": round(wall, 2),
        "step_s": step_s,
    }
    if check_text:
        # the module of the decode step as compiled for this device,
        # from the engine's own jitted step and its live arguments
        facts["decode_has_kernel"] = "tpu_custom_call" in eng._decode_fn.lower(
            eng._params, eng._cache, eng._band("tables"),
            eng._band("tok"), eng._band("pos"), eng._band("alive"),
            eng._band("temps"), eng._band("counts"),
            eng._band("base_keys"), eng._band("limits"),
            eng._band("eos")).compile().as_text()
    return {"tokens": [list(h.tokens) for h in hs],
            "reasons": [h.finish_reason for h in hs], "facts": facts}


def _check_served(tag, res, requests, cfg, want_buckets=None):
    for toks, why, (_, n, _, _) in zip(res["tokens"], res["reasons"],
                                       requests):
        check(why == "budget" and len(toks) == n,
              "%s: a request ended %r after %d of %d tokens"
              % (tag, why, len(toks), n))
        check(all(0 <= t < cfg.vocab for t in toks),
              "%s: token out of the vocabulary" % tag)
    facts = res["facts"]
    check(facts["decode_traces"] == 1,
          "%s: decode step traced %d times" % (tag,
                                               facts["decode_traces"]))
    check(facts["paged_kernel"] == "fused",
          "%s: engine attends through %r" % (tag, facts["paged_kernel"]))
    check(facts.get("decode_has_kernel", True),
          "%s: no tpu_custom_call in the compiled decode step" % tag)
    if want_buckets is not None:
        check(facts["prefill_traces"] == want_buckets,
              "%s: prefill buckets %r, wanted %r"
              % (tag, facts["prefill_traces"], want_buckets))


def _agreement(a, b):
    return float(np.mean(np.asarray(a) == np.asarray(b)))


def _pool_kw(dev, block_bytes, reserve_bytes):
    """Engine option for a block pool that fills what the chip has
    left, less room for the steps' own temporaries (none where the
    backend reports no memory: the engine's default pool)."""
    gc.collect()  # the last engine's pool is free before this one sizes
    stats = dev.memory_stats()
    if not stats:
        return {}
    left = stats["bytes_limit"] - stats["bytes_in_use"] - reserve_bytes
    return {"kv_pool_blocks": max(64, int(left // block_bytes))}


def phase_serve(args, devices):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as tlm

    tiny, dev = args.tiny, devices[0]
    on_chip = dev.platform == "tpu"
    # a CPU engine would pick the XLA gather form: rehearse the kernels
    kernel_kw = {"paged_kernel": "fused"} if tiny else {}
    cfg = _lm_config(tiny, jnp.bfloat16)
    t0 = time.monotonic()
    params = tlm.init_params(cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(params))
    emit(phase="serve", step="model", dim=cfg.dim, heads=cfg.heads,
         layers=cfg.layers, vocab=cfg.vocab, max_len=cfg.max_len,
         dtype="bfloat16", n_params=n_params,
         init_s=round(time.monotonic() - t0, 2))
    rng = np.random.RandomState(args.seed)
    prompts = _prompts(rng, cfg)
    buckets = [8 << i for i in range(len(prompts))]
    new = 8
    Bt = 16  # the engine's default block size

    # --- logits: the kernels against the plain reference -------------
    probe_i = 1 if tiny else 3  # 48 tokens at full width
    probe = prompts[probe_i]
    t0 = time.monotonic()
    lg_bf, nxt, lg_dec_bf = _kernel_logits(params, cfg, probe, Bt)
    ref = _reference_logits(params, cfg, np.append(probe, nxt))
    ref_pre, ref_dec = ref[len(probe) - 1], ref[len(probe)]
    err_pre = float(np.max(np.abs(lg_bf - ref_pre)))
    err_dec = float(np.max(np.abs(lg_dec_bf - ref_dec)))
    # bf16 weights and activations through the whole depth against an
    # f32 reference: logits are O(1) (unit-variance rows of a tied
    # head), bf16 carries 8 bits, so a quarter of a unit bounds honest
    # rounding and is far under what a wrong mask, scale or block would
    # do (those move logits by units)
    tol = 0.25
    emit(phase="serve", step="logits", prompt_tokens=len(probe),
         max_abs_err_prefill=err_pre, max_abs_err_decode=err_dec,
         tolerance=tol, ref_abs_max=float(np.max(np.abs(ref_pre))),
         argmax_agrees=bool(np.argmax(ref_pre) == nxt),
         peak_bytes_in_use=_peak_bytes(dev),
         seconds=round(time.monotonic() - t0, 2))
    check(np.isfinite(lg_bf).all() and np.isfinite(lg_dec_bf).all(),
          "non-finite logits out of the fused kernels")
    check(err_pre <= tol and err_dec <= tol,
          "fused-kernel logits off the f32 reference by %.3f / %.3f "
          "(tolerance %.2f)" % (err_pre, err_dec, tol))

    # --- f32: greedy tokens identical to generate() ------------------
    # the repo's tests require token identity at f32 under full matmul
    # precision; held to that here on a narrow f32 model (Dh = 128, the
    # chip's lane width) through the same fused kernels
    with jax.default_matmul_precision("highest"):
        c32 = tlm.TransformerConfig(
            vocab=cfg.vocab if tiny else 1000, dim=32 if tiny else 256,
            heads=1 if tiny else 2, layers=1 if tiny else 2,
            max_len=32 if tiny else 256,
            dtype=jnp.float32)
        p32 = tlm.init_params(c32, jax.random.PRNGKey(args.seed + 1))
        reqs32 = [(rng.randint(0, c32.vocab, n).astype(np.int32), new,
                   0.0, 0) for n in (5, 11, 20)]
        gen32 = jax.jit(lambda p, t: tlm.generate(p, t, c32, new))
        want = [np.asarray(gen32(p32, jnp.asarray(p)[None])
                           )[0, len(p):].tolist()
                for p, _, _, _ in reqs32]
        res = _drive_engine(p32, c32, reqs32, on_chip, **kernel_kw)
    emit(phase="serve", step="f32_identity", **res["facts"],
         identical=res["tokens"] == want)
    _check_served("f32", res, reqs32, c32)
    check(res["tokens"] == want,
          "f32 greedy tokens differ from generate(): %r vs %r"
          % (res["tokens"], want))

    # --- bf16 oracle tokens, before the pool takes the memory --------
    oracle_for = (0, 1) if tiny else (1, 4)
    gen = jax.jit(lambda p, t: tlm.generate(p, t, cfg, new))
    oracle = {i: np.asarray(gen(params, jnp.asarray(prompts[i])[None])
                            )[0, len(prompts[i]):].tolist()
              for i in oracle_for}
    del gen
    gc.collect()

    # --- the default engine: every bucket, greedy and sampled --------
    greedy = [(p, new, 0.0, 0) for p in prompts]
    sampled = [(prompts[2], new, 0.8, 7), (prompts[2], new, 0.8, 7),
               (prompts[1], new, 1.0, 11)]
    block_bytes = tlm.kv_block_bytes(cfg.layers, cfg.heads,
                                     cfg.dim // cfg.heads, Bt, "none",
                                     act_itemsize=2)
    reserve = 3 << 29  # 1.5 GiB for chunk temporaries and the sampler
    pool = _pool_kw(dev, block_bytes, reserve)
    res = _drive_engine(params, cfg, greedy + sampled, on_chip,
                        **kernel_kw, **pool)
    agree = [_agreement(res["tokens"][i], oracle[i]) for i in oracle_for]
    emit(phase="serve", step="engine_default", **res["facts"],
         greedy_agreement_with_generate=agree,
         peak_bytes_in_use=_peak_bytes(dev))
    _check_served("default", res, greedy + sampled, cfg, buckets)
    g = len(greedy)
    check(res["tokens"][g] == res["tokens"][g + 1],
          "one (prompt, seed) sampled two different continuations")
    # bf16 greedy tokens: two attention formulations (the engine's
    # online-softmax kernels, generate()'s one-shot softmax) may round
    # a near-tie apart and a sequence then parts for good, so the
    # agreement above is reported, not gated. What is gated: the
    # engine's first token is the reference's argmax up to the logits
    # tolerance
    first = res["tokens"][probe_i][0]
    check(ref_pre[first] >= ref_pre.max() - 2 * tol,
          "default engine's first token %d is not the reference's "
          "argmax within tolerance" % first)

    # --- the path that had never compiled: int8 KV --------------------
    short_i = (0, probe_i, len(greedy) - 1)
    short = [greedy[i] for i in short_i]
    short_default = [res["tokens"][i] for i in short_i]
    q_bytes = tlm.kv_block_bytes(cfg.layers, cfg.heads,
                                 cfg.dim // cfg.heads, Bt, "int8")
    res_q = _drive_engine(params, cfg, short, on_chip, kv_quant="int8",
                          **kernel_kw, **_pool_kw(dev, q_bytes, reserve))
    agree_q = [_agreement(a, b)
               for a, b in zip(res_q["tokens"], short_default)]
    emit(phase="serve", step="engine_int8_kv", **res_q["facts"],
         greedy_agreement_with_bf16_engine=agree_q,
         peak_bytes_in_use=_peak_bytes(dev))
    _check_served("int8", res_q, short, cfg)
    # per-block absmax int8 adds up to 1/127 relative error per K/V
    # element on top of bf16's 1/256: twice the logits tolerance
    first = res_q["tokens"][1][0]
    check(ref_pre[first] >= ref_pre.max() - 4 * tol,
          "int8-KV engine's first token %d is not the reference's "
          "argmax within tolerance" % first)

    # --- how a user reaches it: fleet + front door + wire client -----
    from paddle_tpu.analysis.protocol_lint import verify_journal
    from paddle_tpu.serving import FrontDoor, ServingFleet, WireClient

    with tempfile.TemporaryDirectory() as tmp:
        journal = tmp + "/journal.jsonl"
        t0 = time.monotonic()
        fleet = ServingFleet(params, cfg, n_replicas=1,
                             journal_path=journal,
                             heartbeat_timeout_s=600.0,
                             engine_kw={**kernel_kw, **pool})
        fd = FrontDoor(fleet).start()
        try:
            client = WireClient(fd.address, timeout=600.0)
            got = [client.generate_blocking("smoke-%d" % i, prompts[i],
                                            new, stream=bool(i))
                   for i in (0, 2)]
            client.close()
            stats = fleet.stats()
        finally:
            fd.close()
            fleet.close()
        audit = verify_journal(journal, expect_closed=True)
    emit(phase="serve", step="front_door", answered=len(got),
         lost=stats["lost"], completed=stats["completed"],
         journal_findings=[str(d) for d in audit],
         identical_to_engine=[got[j]["tokens"] == res["tokens"][i]
                              for j, i in enumerate((0, 2))],
         seconds=round(time.monotonic() - t0, 2),
         peak_bytes_in_use=_peak_bytes(dev))
    check(stats["lost"] == 0 and stats["completed"] == 2,
          "fleet lost or dropped a request: %r" % stats)
    check(not audit, "journal audit: %r" % [str(d) for d in audit])
    check(sum(got[1]["chunks"], []) == got[1]["tokens"],
          "streamed chunks do not concatenate to the answer")
    check(all(got[j]["tokens"] == res["tokens"][i]
              for j, i in enumerate((0, 2))),
          "front-door tokens differ from the engine's")


# ---------------------------------------------------------------------
# --chips 4: the paths that exist only across chips
# ---------------------------------------------------------------------

def phase_data_parallel(args, devices):
    """ResNet-50 on Executor(mesh={"data": 4}) against the same global
    batch on one device of the same process."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import make_mesh

    batch, hw, classes, depth = _resnet_shape(args.tiny)
    n, steps = len(devices), 3
    mesh = make_mesh({"data": n}, devices=devices)
    main, startup, loss = _resnet_program(fluid, batch, hw, classes, depth)
    feed = _resnet_feed(args.seed, batch, hw, classes)
    runs = {}
    for tag, kw in (("one", {}), ("mesh", {"mesh": mesh})):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace(), **kw)
            exe.run(startup)
            losses, secs = _train_steps(exe, main, loss, feed, steps)
            runs[tag] = {
                "losses": [float(np.ravel(np.asarray(x))[0])
                           for x in losses],
                "secs": secs,
                "param_devices": {len(scope.get(k).devices())
                                  for k in scope.keys()},
                "loss_devices": len(losses[-1].devices()),
            }
            if kw:
                # the module this executor compiled, re-lowered from
                # its cached entry as profiler.compiled_profile does
                exe._capture_avals = True
                exe.run(main, feed=feed, fetch_list=[loss])
                entry, avals, _ = exe._last_exec
                exe._capture_avals, exe._last_exec = False, None
                compiled = entry.lower(*avals).compile()
                feed_sh = compiled.input_shardings[0][1]["image"]
                runs[tag]["all_reduce"] = "all-reduce" in compiled.as_text()
                runs[tag]["feed_devices"] = len(feed_sh.device_set)
                runs[tag]["feed_rows_per_device"] = feed_sh.shard_shape(
                    feed["image"].shape)[0]
    one, par = runs["one"], runs["mesh"]
    # on the scale of the first loss: a fixed batch is memorised within
    # a few steps, and a difference relative to a vanishing loss would
    # measure nothing
    rel = [abs(a - b) / abs(one["losses"][0])
           for a, b in zip(one["losses"], par["losses"])]
    emit(phase="data_parallel", model="resnet%d" % depth, batch=batch,
         devices=n, losses_one_device=one["losses"],
         losses_mesh=par["losses"], rel_diff=rel,
         compile_and_first_step_s=[round(one["secs"][0], 2),
                                   round(par["secs"][0], 2)],
         step_s_median=[float(np.median(one["secs"][1:])),
                        float(np.median(par["secs"][1:]))],
         **{k: par[k] for k in ("all_reduce", "feed_devices",
                                "feed_rows_per_device", "loss_devices")},
         param_devices=sorted(par["param_devices"]),
         peak_bytes_in_use=_peak_bytes(devices[0]))
    check(all(np.isfinite(one["losses"] + par["losses"])),
          "non-finite loss")
    # same program, same seed, same global batch: only the reduction
    # order and bf16 rounding differ between one device and four
    check(max(rel) <= 2e-2, "data-parallel losses diverge: %r" % rel)
    check(par["all_reduce"], "no all-reduce in the data-parallel module")
    check(par["feed_devices"] == n
          and par["feed_rows_per_device"] == batch // n,
          "feeds are not batch-sharded over %d devices" % n)
    check(par["loss_devices"] == n and par["param_devices"] == {n},
          "results do not span %d devices" % n)
    check(one["param_devices"] == {1}, "the one-device run spread out")


def phase_sequence_parallel(args, devices):
    """One 16x2048 transformer train step with flash attention run
    sequence-parallel over a 4-way 'seq' mesh, against the one-chip
    flash step on the same weights and tokens."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.models import transformer as tlm
    from paddle_tpu.parallel import make_mesh

    cfg = _lm_config(args.tiny, jnp.bfloat16)
    B, T = 2, cfg.max_len
    n = len(devices)
    params = tlm.init_params(cfg, jax.random.PRNGKey(args.seed))
    toks = jnp.asarray(np.random.RandomState(args.seed).randint(
        0, cfg.vocab, (B, T + 1)), jnp.int32)
    out = {}
    mesh = make_mesh({"seq": n}, devices=devices)
    for tag, m in (("one", None), ("mesh", mesh)):
        # lr = 1: the update IS the gradient, large enough to survive
        # bf16 weights, so comparing updates compares the backward pass
        step = jax.jit(tlm.make_train_step(cfg, lr=1.0, mesh=m,
                                           attn_impl="flash"))
        p, x = params, toks
        if m is not None:
            rep = NamedSharding(m, P())
            p, x = jax.device_put((params, toks), rep)
        t0 = time.monotonic()
        compiled = step.lower(p, x).compile()
        new_p, loss = compiled(p, x)
        loss.block_until_ready()
        text = compiled.as_text()
        out[tag] = {
            "loss": float(loss), "secs": time.monotonic() - t0,
            "devices": len(loss.devices()),
            "kernel": "tpu_custom_call" in text,
            "a2a": "all-to-all" in text,
            "update": float(jnp.linalg.norm(
                new_p["blocks"][0]["wq"].astype(jnp.float32)
                - params["blocks"][0]["wq"].astype(jnp.float32))),
        }
        del new_p, p, x, step, compiled, text
        gc.collect()
    one, par = out["one"], out["mesh"]
    rel = abs(one["loss"] - par["loss"]) / abs(one["loss"])
    rel_up = abs(one["update"] - par["update"]) / one["update"]
    emit(phase="sequence_parallel", dim=cfg.dim, layers=cfg.layers,
         batch=B, seq_len=T, devices=n, loss_one_chip=one["loss"],
         loss_mesh=par["loss"], rel_diff=rel,
         first_wq_update_norm=[one["update"], par["update"]],
         rel_diff_update=rel_up,
         compile_and_step_s=[round(one["secs"], 2),
                             round(par["secs"], 2)],
         flash_kernel_compiled=[one["kernel"], par["kernel"]],
         all_to_all=par["a2a"], loss_devices=par["devices"],
         peak_bytes_in_use=_peak_bytes(devices[0]))
    check(np.isfinite(one["loss"]) and np.isfinite(par["loss"]),
          "non-finite loss")
    check(rel <= 2e-2, "sequence-parallel loss off the one-chip "
          "step's by %.4f" % rel)
    check(one["update"] > 0 and rel_up <= 5e-2,
          "first block's wq update differs: %r vs %r"
          % (one["update"], par["update"]))
    check(par["devices"] == n, "the step did not span %d devices" % n)
    check(par["a2a"], "no all-to-all in the sequence-parallel module")
    if devices[0].platform == "tpu":
        check(one["kernel"] and par["kernel"],
              "no tpu_custom_call: flash attention did not compile in")


# ---------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip paths")
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes on whatever JAX finds (a CPU "
                         "rehearsal); never a chip result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # the backend's own start-up failure
        emit(phase="device", error="%s: %s" % (type(e).__name__, e))
        return 1
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu" and not args.tiny:
        emit(phase="device", error="no TPU: JAX found %r; the only CPU "
             "mode is the --tiny rehearsal" % (device,))
        return 1
    if len(devices) < args.chips:
        emit(phase="device", error="--chips %d but JAX found %r"
             % (args.chips, device))
        return 1
    # the script alone, without the program, stops here
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache_hits.append(name)
        if name == "/jax/compilation_cache/cache_hits" else None)
    emit(phase="device", jax=jax.__version__, tiny=args.tiny,
         compile_cache_dir=enable_compile_cache(), **device)

    phases = ([phase_train, phase_cli, phase_serve] if args.chips == 1
              else [phase_data_parallel, phase_sequence_parallel])
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.monotonic()
        try:
            phase(args, devices[:args.chips])
            ok = True
        except Exception:  # report it, run the rest, fail at the end
            traceback.print_exc()
            failed.append(name)
            ok = False
        gc.collect()
        emit(phase=name, ok=ok, seconds=round(time.monotonic() - t0, 2),
             peak_bytes_in_use=_peak_bytes(dev))
    emit(phase="summary", failed=failed,
         persistent_cache_hits=len(cache_hits))
    if failed:
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
