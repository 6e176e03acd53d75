"""The latent-attention sparse-expert family's serving cells: the
sparse-expert driver's loop around a `ServingEngine` that serves an
`MlaMoeConfig`.

The loop, the window, the traced slice's early profiler start, the
per-step `cache_bytes_per_slot` reading, the topped-up sample, the
per-step `moe_experts_hit` log and `check()` (with `gap_mean`) are
`drivers/serve_engine_afmoe.py`'s and `drivers/serve_engine_hybrid.py`'s,
unchanged. What this driver brings:

  * the engine is built from the configuration's `shape` as an
    `MlaMoeConfig`: no other option than the configuration's `engine`
    group;
  * the model's operations (`model_flops`, what `mfu.serve` reads) are
    counted with `lib/costs_mla_moe.py`, in the form each path
    computes: absorbed in decode, expanded in prefill; a token is
    ROUTED through the shared expert and top_k x held / n_experts of
    this chip's experts;
  * the (token, choice) pairs logged beside each decode step's
    `moe_experts_hit` (what `moe_expert_roofline` divides by) are the
    pairs that reach the experts HELD here: top_k x held / n_experts of
    a token's, the expected share (the engine counts the experts
    reached, not their rows; the rows are ~1 % of the products' bytes);
  * the notes carry this family's counters (`moe_experts_hit` a layer
    and step, `moe_rows_max`, `cache_bytes_in_use`: the one `full` kind,
    the latent pools) and `chunk_step_share_pct`, and the step's
    contexts are logged per step as the base driver logs them.
"""

from __future__ import annotations

from lib import costs_mla_moe as costs

from drivers import serve_engine as base
from drivers import serve_engine_afmoe as afmoe
from drivers import serve_engine_hybrid as hybrid


class Driver(afmoe.Driver):
    def __init__(self, ctx):
        # a program without the family (a parent commit) fails here, at
        # once, before any weight is made
        from paddle_tpu.models import mla_moe  # noqa: F401

        hybrid.Driver.__init__(self, ctx)
        self.moe_steps = []
        self.pairs_a_token = (int(self.shape["top_k"])
                              * costs.held_share(self.shape)
                              * costs.layer_counts(self.shape)["expert"])

    def build_engine(self, **extra):
        import jax.numpy as jnp

        from paddle_tpu.models.mla_moe import MlaMoeConfig
        from paddle_tpu.serving import ServingEngine

        cfg = MlaMoeConfig(
            max_len=self.max_len, dtype=jnp.dtype(self.ctx.config["dtype"]),
            **self.shape)
        return ServingEngine(self.params, cfg,
                             **{**self.engine_kw, **extra})

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
        ctxs = [c for s in steps for c in s[1]]
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
            "decode_context_mean": (sum(ctxs) / len(ctxs) if ctxs else None),
            "cache_bytes_in_use": m.cache_bytes_in_use,
            "kv_blocks_in_use_of": [int(m.kv_blocks_in_use),
                                    int(m.kv_blocks_total)],
        }
        run["notes"].update(notes)
        self.ctx.log("mla_moe: " + repr(notes))
        return run
