"""Serving cells: the benchmark's own loop around `ServingEngine`.

The timed entry is `ServingEngine.submit()` + `step()`, called from
this process's one thread: no front door, fleet or wire client.

Closed loop ("loop": "closed"): `clients` requests are always in
flight; a client's next request is submitted when its last finishes.
Set-up steps the engine until every first request has emitted
`ramp_output_tokens`, so the window opens on a full batch in the
middle of long generations.

Open loop ("loop": "open"): requests fall due on a schedule drawn from
the seed, whether or not earlier ones have finished, and each is
submitted when due, between two `step()` calls. The schedule starts
`lead_in_s` before the window; first-token time counts from the
instant a request was DUE. After the window closes the loop goes on,
submitting nothing new, until every request due in the window has
finished (at most `drain_s`): a late answer is late, not wrong.

What the window reports (all of the window's work over all of its
time; tails over every request):
  serve_tok_s     output tokens emitted in the window / window seconds
  itl_p95_ms      95th percentile of every gap between consecutive
                  output tokens emitted in the window
and, in the notes only (none is steady enough to judge, PERF.md):
first-token times counted from the instant a request was DUE, over
the requests due in the window, as percentiles and as the share that
came within each of a few limits; queue_wait_p95_ms. The same times,
request by request, go to standard error
"""

from __future__ import annotations

import gc
import time

import numpy as np

from lib import costs, stats, traffic


TRACED_SPAN = "bench.traced"
_COMPILES = []  # one listener per process, whatever number of drivers


def _count_compiles():
    import jax

    if not _COMPILES:
        _COMPILES.append("listening")
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: _COMPILES.append(name)
            if name == "/jax/core/compile/backend_compile_duration" else None)


class _Req(object):
    __slots__ = ("spec", "handle", "due", "submitted", "t_first", "t_last",
                 "seen", "t_done", "tokens", "reason", "queue_wait_s")

    def __init__(self, spec, due):
        self.spec, self.due = spec, due
        self.handle = None
        self.submitted = self.t_first = self.t_last = self.t_done = None
        self.tokens = self.reason = self.queue_wait_s = None
        self.seen = 0

    def close(self):
        """Keep what the request produced, let go of the engine."""
        h, self.handle = self.handle, None
        if h is not None:
            self.tokens = np.asarray(h.tokens, np.int32)
            self.reason, self.queue_wait_s = h.finish_reason, h.queue_wait_s


class Driver(object):
    def __init__(self, ctx):
        self.ctx = ctx
        self.w = ctx.workload
        self.shape = ctx.config["shape"]
        self.max_len = int(ctx.config["max_len"])
        self.engine_kw = dict(ctx.config["engine"])
        self.ref = ctx.load_module("references", ctx.config["reference"])
        self.eng = self.params = None

    # ------------------------------------------------------------------
    def build_engine(self, **extra):
        import jax.numpy as jnp

        from paddle_tpu.models.transformer import TransformerConfig
        from paddle_tpu.serving import ServingEngine

        cfg = TransformerConfig(
            vocab=self.shape["vocab"], dim=self.shape["dim"],
            heads=self.shape["heads"], layers=self.shape["layers"],
            mlp_mult=self.shape["mlp_mult"], max_len=self.max_len,
            dtype=jnp.dtype(self.ctx.config["dtype"]))
        return ServingEngine(self.params, cfg,
                             **{**self.engine_kw, **extra})

    def setup(self):
        import jax

        ctx, tr = self.ctx, self.w["traffic"]
        t0 = time.monotonic()
        self.params = self.ref.init_weights(
            self.shape, self.max_len, ctx.seed,
            dtype=ctx.config["dtype"])
        jax.block_until_ready(self.params)
        t1 = time.monotonic()
        self.eng = self.build_engine()
        self.block_tokens = int(self.eng.kv_block_tokens)
        self.requests = traffic.generate(tr, self.shape["vocab"], ctx.seed,
                                         ctx.seconds)
        # warm up the shapes this cell's traffic uses and no others: one
        # prompt per prefill bucket, two tokens each (the second comes
        # from the decode step)
        rng = np.random.default_rng(ctx.seed + 1)
        buckets = traffic.prompt_buckets(tr, int(self.eng.min_bucket),
                                         self.max_len, ctx.seconds)
        for b in buckets:
            n = min(b, self.max_len - 2)
            self.eng.submit(rng.integers(0, self.shape["vocab"], n,
                                         dtype=np.int32), 2)
        self.eng.run()
        t2 = time.monotonic()
        ctx.log("set-up: weights %.1f s, engine + warm-up of buckets %r "
                "%.1f s" % (t1 - t0, buckets, t2 - t1))
        _count_compiles()
        self.compiles = _COMPILES
        self.n_compiles_setup = len(_COMPILES)

    # ------------------------------------------------------------------
    def _submit(self, req, now):
        s = req.spec
        with self.ctx.span("sched.admit"):
            req.handle = self.eng.submit(
                s["prompt"], s["max_new"], temperature=s["temperature"],
                seed=s["seed"])
        req.submitted = now
        if req.due is None:
            req.due = now
        self.live.append(req)

    def _step(self):
        """One engine step, then what it emitted: per-request token
        times, the contexts its decode attended over, finished requests."""
        pre0 = self.eng.metrics.prefill_tokens_computed
        t_before = time.monotonic()
        with self.ctx.span("sched.step"):
            self.eng.step()
        now = time.monotonic()
        ctxs, still = [], []
        for r in self.live:
            n = len(r.handle.tokens)
            new = n - r.seen
            if new:
                # the step that ends a prefill also decodes that slot:
                # its first two tokens come out together
                decoded = new if r.seen else new - 1
                first_ctx = len(r.spec["prompt"]) + max(r.seen, 1)
                ctxs.extend(first_ctx + i for i in range(decoded))
                if r.t_first is None:
                    r.t_first = now
                    self.gaps.extend([(now, 0.0)] * (new - 1))
                else:
                    self.gaps.extend([(now, (now - r.t_last) / new)] * new)
                self.emitted.append((now, new))
                r.t_last, r.seen = now, n
            if r.handle.done:
                r.t_done = now
                r.close()
            else:
                still.append(r)
        self.live = still
        self.steps.append((now, ctxs,
                           self.eng.metrics.prefill_tokens_computed - pre0,
                           now - t_before))
        return now

    def window(self):
        ctx, tr = self.ctx, self.w["traffic"]
        eng = self.eng
        self.live, self.steps, self.gaps, self.emitted = [], [], [], []
        reqs = [_Req(s, s["due_s"]) for s in self.requests]
        nxt = 0
        open_loop = tr["loop"] == "open"
        lateness = []

        # --- lead-in: still set-up ---------------------------------------
        # starting the profiler stalls this thread for seconds: an open
        # loop would open its window on the backlog of that stall, so
        # there it starts before the lead-in, which drains it
        if ctx.trace and open_loop:
            ctx.start_trace()
        if open_loop:
            origin = time.monotonic()
            t_open = origin + float(tr["lead_in_s"])
            for r in reqs:
                r.due = origin + r.due
        else:
            for _ in range(int(tr["clients"])):
                self._submit(reqs[nxt], time.monotonic())
                nxt += 1
            first = list(self.live)
            ramp = int(tr["ramp_output_tokens"])
            while any(r.seen < ramp and r.t_done is None for r in first):
                self._step()
                while len(self.live) < int(tr["clients"]):
                    self._submit(reqs[nxt], time.monotonic())
                    nxt += 1
            t_open = time.monotonic()

        def pump(until, submit):
            """Drive the loop until `until` seconds on the clock."""
            nonlocal nxt
            while True:
                now = time.monotonic()
                if now >= until:
                    return now
                if open_loop:
                    while submit and nxt < len(reqs) and reqs[nxt].due <= now:
                        lateness.append(now - reqs[nxt].due)
                        self._submit(reqs[nxt], now)
                        nxt += 1
                    if not self.live:
                        more = submit and nxt < len(reqs)
                        wake = min(reqs[nxt].due, until) if more else until
                        with ctx.span("loadgen.wait_due"):
                            time.sleep(max(0.0, wake - time.monotonic()))
                        continue
                else:
                    while submit and len(self.live) < int(tr["clients"]):
                        if nxt >= len(reqs):
                            raise RuntimeError(
                                "the cell's traffic has too few requests "
                                "(%d) for this window" % len(reqs))
                        self._submit(reqs[nxt], now)
                        nxt += 1
                self._step()

        if open_loop:
            pump(t_open, True)
            t_open = time.monotonic()
        # --- the measured window -----------------------------------------
        setup_s = t_open - ctx.t_start
        n_compiles0 = len(self.compiles)
        occ0 = (eng.metrics.occupancy.count, eng.metrics.occupancy.total)
        traced = None
        if ctx.trace:
            t_trace = float(self.w["trace_seconds"])
            if not open_loop:
                ctx.start_trace()
            with ctx.span(TRACED_SPAN):  # what the reduction reads
                tr0 = time.monotonic()
                pump(min(tr0 + t_trace, t_open + ctx.seconds), True)
                traced = (tr0, time.monotonic())
            occ0 = (eng.metrics.occupancy.count - occ0[0],
                    eng.metrics.occupancy.total - occ0[1])
            # writing the trace out stalls the loop again, for longer: the
            # rest of this window is disturbed, so a traced run reads ALL
            # its per-layer numbers over the traced slice. The loop first
            # runs on a little, so that what fell due in the slice is
            # admitted before the stall
            pump(min(traced[1] + 1.5, t_open + ctx.seconds), True)
            ctx.stop_trace()
            traced_window_s = traced[1] - traced[0]
        t_close = pump(t_open + ctx.seconds, True)
        occupancy = occ0 if traced else (
            eng.metrics.occupancy.count - occ0[0],
            eng.metrics.occupancy.total - occ0[1])
        compiles_in_window = len(self.compiles) - n_compiles0
        # --- after the close: late answers are late, not wrong -----------
        if open_loop:
            due_in = [r for r in reqs if t_open <= r.due < t_close]
            limit = time.monotonic() + float(tr["drain_s"])
            while (any(r.t_done is None for r in due_in)
                   and time.monotonic() < limit):
                while nxt < len(reqs) and reqs[nxt].due < t_close:
                    self._submit(reqs[nxt], time.monotonic())
                    nxt += 1
                if not self.live:
                    break
                self._step()
        else:
            due_in = [r for r in reqs if r.submitted is not None
                      and r.submitted < t_close
                      and (r.t_done is None or r.t_done >= t_open)]

        window_s = t_close - t_open
        tokens = sum(n for t, n in self.emitted if t_open <= t <= t_close)
        gaps = [g for t, g in self.gaps if t_open <= t <= t_close]
        ok = [r for r in due_in if r.t_done is not None
              and r.reason in ("budget", "eos")]
        failed = len(due_in) - len(ok) if open_loop else sum(
            1 for r in due_in if r.t_done is not None
            and r.reason not in ("budget", "eos"))
        e2e = {"serve_tok_s": tokens / window_s,
               "itl_p95_ms": 1e3 * stats.percentile(gaps, 95),
               "setup_s": setup_s}
        halves = {}
        if open_loop:
            # a request that failed has no first token: it misses any limit
            ttft = [(r.t_first - r.due) if r.t_first is not None
                    else float(tr["drain_s"]) + ctx.seconds for r in due_in]
            # recorded, not judged: the tail is the requests that found
            # every slot taken, and how many do is the seed's order
            # (PERF.md); one host stall moves it by a factor besides
            for q in (50, 75, 90, 95):
                halves["ttft_p%d_ms" % q] = 1e3 * stats.percentile(ttft, q)
            ctx.log("ttft [due s after the open, prompt tokens, ms]: " + repr(
                [[round(r.due - t_open, 3), len(r.spec["prompt"]),
                  round(1e3 * x, 1)] for r, x in zip(due_in, ttft)]))
            halves["ttft_share_within_ms"] = {
                str(ms): 100.0 * sum(1 for x in ttft if 1e3 * x <= ms)
                / len(ttft) for ms in (100, 150, 200, 250, 300, 400, 500)}
            waits = [r.queue_wait_s for r in due_in
                     if r.queue_wait_s is not None]
            halves["queue_wait_p95_ms"] = (
                1e3 * stats.percentile(waits, 95) if waits else None)
            # a backlog that grows shows as a second half slower than the first
            mid = (t_open + t_close) / 2
            for tag, part in (("first", [x for r, x in zip(due_in, ttft)
                                         if r.due < mid]),
                              ("second", [x for r, x in zip(due_in, ttft)
                                          if r.due >= mid])):
                halves["ttft_p50_%s_half_ms" % tag] = (
                    1e3 * stats.percentile(part, 50) if part else None)
            halves["unfinished_at_close"] = sum(
                1 for r in due_in if r.t_done is None or r.t_done > t_close)
        self.window_reqs = ok
        in_win = [s for s in self.steps if t_open <= s[0] <= t_close]
        # what the per-layer readers read: over the traced slice in a
        # traced run, over the window otherwise
        lo, hi = traced or (t_open, t_close)
        flops = sum(costs.lm_decode_flops(self.shape, c)
                    for t, ctxs, _, _ in self.steps if lo <= t <= hi
                    for c in ctxs)
        # prefill work: true prompt tokens of requests whose prefill ran
        # there (first token emitted there)
        flops += sum(costs.lm_prefill_flops(self.shape, len(r.spec["prompt"]))
                     for r in reqs if r.t_first is not None
                     and lo <= r.t_first <= hi)
        notes = {
            "window_s": window_s, "steps": len(in_win),
            "requests_in_window": len(due_in), "finished_ok": len(ok),
            "tokens": tokens, "gaps": len(gaps),
            "compiles_in_window": compiles_in_window,
            "generator_lateness_p95_ms": (
                1e3 * stats.percentile(lateness, 95) if lateness else None),
            "itl_p50_ms": 1e3 * stats.percentile(gaps, 50),
            "slot_occupancy_pct": (100.0 * occupancy[1] / occupancy[0]
                                   if occupancy[0] else None),
        }
        notes.update(halves)
        # the slowest steps since set-up's warm-up, lead-in or ramp included:
        # [ms, seconds after the window opened, prompt tokens, decoding slots]
        notes["slowest_steps"] = [
            [round(1e3 * d, 1), round(t - t_open, 2), pre, len(c)]
            for t, c, pre, d in sorted(self.steps, key=lambda x: -x[3])[:4]]
        notes["compiles_before_window"] = n_compiles0 - self.n_compiles_setup
        # the longest stretches outside engine.step(): this loop's own work
        # or a stall of the whole process [ms, seconds after the open]
        outside = [(b[0] - b[3] - a[0], b[0] - t_open)
                   for a, b in zip(self.steps, self.steps[1:])]
        notes["longest_outside_step"] = [
            [round(1e3 * d, 1), round(t, 2)]
            for d, t in sorted(outside, reverse=True)[:3]]
        ctx.log("window: " + repr(notes))
        return {
            "end_to_end": e2e, "attempted": len(due_in), "failed": failed,
            "notes": notes, "traced_window_s": (
                traced_window_s if traced else None),
            "traced": traced, "traced_span": TRACED_SPAN,
            "window": (t_open, t_close), "layer_scope": (lo, hi),
            "steps": self.steps, "model_flops": flops,
            "occupancy": occupancy,
            "block_tokens": self.block_tokens, "shape": self.shape,
            "max_slots": int(eng.max_slots),
        }

    # ------------------------------------------------------------------
    def release(self):
        """Free the engine and its pool; the weights stay for the check."""
        for r in self.live:
            r.close()
        self.live = []
        self.eng = None
        gc.collect()

    def sample(self):
        """Greedy requests the window finished: the longest, and others
        drawn from the seed, `check.requests` at the most (all of them
        where the window finished no more than that)."""
        greedy = [r for r in self.window_reqs
                  if r.spec["temperature"] == 0.0]
        if not greedy:
            return []
        greedy.sort(key=lambda r: -(len(r.spec["prompt"])
                                    + len(r.tokens)))
        rng = np.random.default_rng(self.ctx.seed + 2)
        k = min(int(self.w["check"]["requests"]), len(greedy))
        rest = [greedy[i] for i in
                1 + rng.permutation(len(greedy) - 1)[:k - 1]]
        return [greedy[0]] + rest

    def check(self, control=None):
        """The number compared is the square of the mean gap of the
        served tokens that are NOT the reference's first: it grows with
        the variance of the noise on the logits and with nothing else.
        (The mean gap over ALL tokens also grows with how many of the
        seed's logits lie near a tie, which differs threefold from seed
        to seed, for the program and the control alike: PERF.md.)"""
        chk = self.w["check"]
        worst, total, tokens, flips = 0.0, 0.0, 0, 0
        sample = self.sample()
        for r in sample:
            g = self.ref.served_gap(
                self.params, self.shape, r.spec["prompt"], r.tokens,
                int(chk["pad_to"]), control=control)
            worst, total = max(worst, g["max"]), total + g["sum"]
            tokens, flips = tokens + g["n"], flips + g["flips"]
        flip_gap = total / flips if flips else 0.0
        compared = {
            "flip_gap_mean_sq": {"value": flip_gap ** 2,
                                 "limit": chk["flip_gap_mean_sq_limit"]},
            "tokens_compared": {"value": tokens, "limit": chk["min_tokens"]},
        }
        # read for PERF.md, not compared
        self.widest_gap = worst
        self.gap_mean = total / tokens if tokens else float("inf")
        self.ctx.log("check: %d tokens of %d requests compared, %d not the "
                     "reference's first, their mean gap %.6f, mean gap of "
                     "all %.6f, widest gap %.6f"
                     % (tokens, len(sample), flips, flip_gap, self.gap_mean,
                        worst))
        correct = (flip_gap ** 2 <= chk["flip_gap_mean_sq_limit"]
                   and tokens >= chk["min_tokens"])
        return bool(correct), compared
