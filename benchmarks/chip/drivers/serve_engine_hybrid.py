"""The hybrid family's serving cells: `serve_engine`'s loop around a
`ServingEngine` that serves a SambaY configuration.

The loop, the window, what is timed and what `check()` compares are
`drivers/serve_engine.py`'s, unchanged: `ServingEngine.submit()` +
`step()` from this process's one thread. What this driver brings:

  * the engine is built from the configuration's `shape` as a
    `SambaYConfig` (vocab, dim, heads, kv_heads, layers, mlp_mult,
    window): no other option than the configuration's `engine` group;
  * the model's operations (`model_flops`, what `mfu.serve` reads) are
    counted by kind of layer with `lib/costs_sambay.py`;
  * in a traced closed-loop run the profiler starts `trace_lead_steps`
    engine steps BEFORE the ramp ends (the cell's file), as the open
    loop starts it before its lead-in: starting it stalls this thread
    for seconds, and started at the window's open the traced slice is
    mostly that stall (PERF.md section 5, the ledger's PR 26 line of
    `gpt1p3b_decode_closed`);
  * per decode step the engine's `cache_bytes_per_slot` counter (bytes
    resident in the three caches over the live slots) is read beside
    the step, and reported over the scope the per-layer readers use
    as `cache_bytes_per_slot` (count, sum) for `counter_mean`;
  * the notes gain `chunk_step_share_pct`: the share of the window's
    engine steps that carried a prefill chunk (what `itl_p95_ms` can
    or cannot see of prefill);
  * the sample `check()` compares is topped up, where the window
    finished fewer greedy requests than `check.requests`, with the
    greedy requests still decoding at its close, those furthest along
    first, on the tokens they had emitted: outputs here are 1,024
    tokens and more, and a traced run, whose window loses seconds to
    writing the trace out, may finish none. More tokens are compared,
    never fewer; every one was served in or before the window.
"""

from __future__ import annotations

import time

from lib import costs_sambay

from drivers import serve_engine as base


class _TraceOnce(object):
    """The harness's context, with a profiler that starts once."""

    def __init__(self, ctx):
        self._ctx = ctx
        self.trace_started = False

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def start_trace(self):
        if not self.trace_started:
            self.trace_started = True
            self._ctx.start_trace()


class Driver(base.Driver):
    def __init__(self, ctx):
        # a program without the family (a parent commit) fails here, at
        # once, before any weight is made
        from paddle_tpu.models import sambay  # noqa: F401

        base.Driver.__init__(self, _TraceOnce(ctx))
        self.cache_stat = []
        self.unfinished = []

    def build_engine(self, **extra):
        import jax.numpy as jnp

        from paddle_tpu.models.sambay import SambaYConfig
        from paddle_tpu.serving import ServingEngine

        s = self.shape
        cfg = SambaYConfig(
            vocab=s["vocab"], dim=s["dim"], heads=s["heads"],
            kv_heads=s["kv_heads"], layers=s["layers"],
            mlp_mult=s["mlp_mult"], window=s["window"], max_len=self.max_len,
            dtype=jnp.dtype(self.ctx.config["dtype"]))
        return ServingEngine(self.params, cfg,
                             **{**self.engine_kw, **extra})

    def _submit(self, req, now):
        base.Driver._submit(self, req, now)
        self.submitted.append(req)

    def _step(self):
        ctx, tr = self.ctx, self.w["traffic"]
        if ctx.trace and not ctx.trace_started and tr["loop"] == "closed":
            # still the ramp: every first request is live (none can
            # finish inside it) and the slowest has this far to go
            left = int(tr["ramp_output_tokens"]) - min(
                r.seen for r in self.live)
            if left <= int(self.w["trace_lead_steps"]):
                t0 = time.monotonic()
                ctx.start_trace()
                ctx.log("profiler started %d steps before the ramp ends "
                        "(%.1f s)" % (left, time.monotonic() - t0))
        now = base.Driver._step(self)
        stat = self.eng.metrics.cache_bytes_per_slot
        self.cache_stat.append((now, stat.count, stat.total))
        return now

    def release(self):
        # the engine goes; what the unfinished requests had emitted stays
        self.unfinished = [r for r in self.live
                           if r.spec["temperature"] == 0.0]
        base.Driver.release(self)

    def sample(self):
        picked = base.Driver.sample(self)
        short = int(self.w["check"]["requests"]) - len(picked)
        rest = sorted((r for r in self.unfinished if len(r.tokens) >= 64),
                      key=lambda r: -len(r.tokens))
        return picked + rest[:max(0, short)]

    def window(self):
        self.submitted, self.cache_stat = [], []
        run = base.Driver.window(self)
        lo, hi = run["layer_scope"]
        shape = self.shape
        flops = sum(costs_sambay.decode_flops(shape, c)
                    for t, ctxs, _, _ in run["steps"] if lo <= t <= hi
                    for c in ctxs)
        flops += sum(costs_sambay.prefill_flops(shape, len(r.spec["prompt"]))
                     for r in self.submitted if r.t_first is not None
                     and lo <= r.t_first <= hi)
        run["model_flops"] = flops
        inside = [(n, tot) for t, n, tot in self.cache_stat if lo <= t <= hi]
        if len(inside) > 1:
            run["cache_bytes_per_slot"] = (inside[-1][0] - inside[0][0],
                                           inside[-1][1] - inside[0][1])
        t_open, t_close = run["window"]
        steps = [s for s in run["steps"] if t_open <= s[0] <= t_close]
        with_chunk = sum(1 for s in steps if s[2])
        run["notes"]["chunk_step_share_pct"] = (
            100.0 * with_chunk / len(steps) if steps else None)
        run["notes"]["steps_with_chunk"] = with_chunk
        m = self.eng.metrics
        run["notes"]["window_blocks_released"] = m.window_blocks_released
        run["notes"]["state_slots_reset"] = m.state_slots_reset
        run["notes"]["cache_bytes_in_use"] = m.cache_bytes_in_use
        self.ctx.log("hybrid: " + repr({k: run["notes"][k] for k in (
            "chunk_step_share_pct", "steps_with_chunk",
            "window_blocks_released", "state_slots_reset",
            "cache_bytes_in_use")}))
        return run
