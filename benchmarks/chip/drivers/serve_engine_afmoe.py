"""The sparse-expert family's serving cells: the hybrid driver's loop
around a `ServingEngine` that serves an `AfmoeConfig`.

The loop, the window, the traced slice's early profiler start, the
per-step `cache_bytes_per_slot` reading, the topped-up sample and
`check()` are `drivers/serve_engine_hybrid.py`'s, unchanged. What this
driver brings:

  * the engine is built from the configuration's `shape` as an
    `AfmoeConfig`: no other option than the configuration's `engine`
    group;
  * the model's operations (`model_flops`, what `mfu.serve` reads) are
    counted by kind of layer with `lib/costs_afmoe.py`, with the
    parameters a token is ROUTED through: top_k experts and the shared
    one, not all that are held;
  * per decode step read, the engine's `moe_experts_hit` counter (the
    distinct experts the step's live rows reached, summed over the
    expert layers; it rides the step's one packed result) is logged
    beside the step's contexts, with the (token, choice) pairs those
    contexts make: `run["moe_steps"]`, what `moe_expert_roofline`
    divides by;
  * `check()` compares one number more: `gap_mean`, the mean gap
    between the reference's best logit and the served token's over ALL
    compared tokens, against `check.gap_mean_limit`. Here a served
    token that is not the reference's first mostly follows from an
    expert chosen otherwise at a near-tie, which moves a logit by what
    an expert weighs, whatever the precision: the size of such a gap
    (`flip_gap_mean_sq`) tells the program from a lower precision by
    a factor under two, their number by five (PERF.md section 6);
  * the notes carry this family's counters (`moe_experts_hit` a layer
    and step, `moe_rows_max`, `window_blocks_released`,
    `cache_bytes_in_use` by kind: `full` and `window`, no `state`) and
    `chunk_step_share_pct`.
"""

from __future__ import annotations

from lib import costs_afmoe as costs

from drivers import serve_engine as base
from drivers import serve_engine_hybrid as hybrid


class Driver(hybrid.Driver):
    def __init__(self, ctx):
        # a program without the family (a parent commit) fails here, at
        # once, before any weight is made
        from paddle_tpu.models import afmoe  # noqa: F401

        hybrid.Driver.__init__(self, ctx)
        self.moe_steps = []
        self.pairs_a_token = (int(self.shape["top_k"])
                              * costs.layer_counts(self.shape)["expert"])

    def build_engine(self, **extra):
        import jax.numpy as jnp

        from paddle_tpu.models.afmoe import AfmoeConfig
        from paddle_tpu.serving import ServingEngine

        cfg = AfmoeConfig(
            max_len=self.max_len, dtype=jnp.dtype(self.ctx.config["dtype"]),
            **self.shape)
        return ServingEngine(self.params, cfg,
                             **{**self.engine_kw, **extra})

    def _step(self):
        hit = self.eng.metrics.moe_experts_hit
        n0, total0 = hit.count, hit.total
        now = hybrid.Driver._step(self)
        if hit.count > n0:
            # the step just read: its tokens' contexts are the last entry
            self.moe_steps.append(
                (now, len(self.steps[-1][1]) * self.pairs_a_token,
                 hit.total - total0))
        return now

    def window(self):
        self.submitted, self.cache_stat, self.moe_steps = [], [], []
        # the base window first counts a GPT block's operations off
        # `mlp_mult`, a key this family's shape has no use for: it is
        # lent one for the call, and the count is replaced below
        shape = self.shape
        self.shape = dict(shape, mlp_mult=0)
        try:
            run = base.Driver.window(self)
        finally:
            self.shape = shape
        run["shape"] = shape
        lo, hi = run["layer_scope"]
        flops = sum(costs.decode_flops(shape, c)
                    for t, ctxs, _, _ in run["steps"] if lo <= t <= hi
                    for c in ctxs)
        flops += sum(costs.prefill_flops(shape, len(r.spec["prompt"]))
                     for r in self.submitted if r.t_first is not None
                     and lo <= r.t_first <= hi)
        run["model_flops"] = flops
        run["moe_steps"] = self.moe_steps
        inside = [(n, tot) for t, n, tot in self.cache_stat if lo <= t <= hi]
        if len(inside) > 1:
            run["cache_bytes_per_slot"] = (inside[-1][0] - inside[0][0],
                                           inside[-1][1] - inside[0][1])
        t_open, t_close = run["window"]
        steps = [s for s in run["steps"] if t_open <= s[0] <= t_close]
        with_chunk = sum(1 for s in steps if s[2])
        hits = [h for t, _, h in self.moe_steps if t_open <= t <= t_close]
        m = self.eng.metrics
        layers = costs.layer_counts(shape)["expert"]
        notes = {
            "chunk_step_share_pct": (100.0 * with_chunk / len(steps)
                                     if steps else None),
            "steps_with_chunk": with_chunk,
            "moe_experts_hit_a_layer_step": (
                sum(hits) / len(hits) / layers if hits else None),
            "moe_rows_max": (m.moe_rows_max.mean if m.moe_rows_max.count
                             else None),
            "window_blocks_released": m.window_blocks_released,
            "state_slots_reset": m.state_slots_reset,
            "cache_bytes_in_use": m.cache_bytes_in_use,
            "kv_blocks_in_use_of": [int(m.kv_blocks_in_use),
                                    int(m.kv_blocks_total)],
        }
        run["notes"].update(notes)
        self.ctx.log("afmoe: " + repr(notes))
        return run

    def check(self, control=None):
        correct, compared = hybrid.Driver.check(self, control=control)
        limit = self.w["check"]["gap_mean_limit"]
        compared["gap_mean"] = {"value": self.gap_mean, "limit": limit}
        return bool(correct and self.gap_mean <= limit), compared
