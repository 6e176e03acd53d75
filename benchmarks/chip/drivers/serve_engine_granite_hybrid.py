"""The Mamba-2 / grouped-query hybrid's serving cells: the hybrid
driver's loop around a `ServingEngine` that serves a
`GraniteHybridConfig`.

The loop, the window, the traced slice's early profiler start, the
per-step `cache_bytes_per_slot` reading, the topped-up sample and
`check()` are `drivers/serve_engine_hybrid.py`'s, unchanged. What this
driver brings:

  * the engine is built from the configuration's `shape` as a
    `GraniteHybridConfig`: no other option than the configuration's
    `engine` group;
  * the model's operations (`model_flops`, what `mfu.serve` reads) are
    counted by kind of layer with `lib/costs_granite_hybrid.py`;
  * the notes carry this family's counters (`state_slots_reset`,
    `cache_bytes_in_use` by kind: no `window` kind here) and
    `chunk_step_share_pct`.
"""

from __future__ import annotations

from lib import costs_granite_hybrid as costs

from drivers import serve_engine as base
from drivers import serve_engine_hybrid as hybrid


class Driver(hybrid.Driver):
    def __init__(self, ctx):
        # a program without the family (a parent commit) fails here, at
        # once, before any weight is made
        from paddle_tpu.models import granite_hybrid  # noqa: F401

        hybrid.Driver.__init__(self, ctx)

    def build_engine(self, **extra):
        import jax.numpy as jnp

        from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
        from paddle_tpu.serving import ServingEngine

        cfg = GraniteHybridConfig(
            max_len=self.max_len, dtype=jnp.dtype(self.ctx.config["dtype"]),
            **self.shape)
        return ServingEngine(self.params, cfg,
                             **{**self.engine_kw, **extra})

    def window(self):
        self.submitted, self.cache_stat = [], []
        run = base.Driver.window(self)
        lo, hi = run["layer_scope"]
        shape = self.shape
        flops = sum(costs.decode_flops(shape, c)
                    for t, ctxs, _, _ in run["steps"] if lo <= t <= hi
                    for c in ctxs)
        flops += sum(costs.prefill_flops(shape, len(r.spec["prompt"]))
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
        m = self.eng.metrics
        notes = {
            "chunk_step_share_pct": (100.0 * with_chunk / len(steps)
                                     if steps else None),
            "steps_with_chunk": with_chunk,
            "state_slots_reset": m.state_slots_reset,
            "cache_bytes_in_use": m.cache_bytes_in_use,
            "kv_blocks_in_use_of": [int(m.kv_blocks_in_use),
                                    int(m.kv_blocks_total)],
        }
        run["notes"].update(notes)
        self.ctx.log("granite hybrid: " + repr(notes))
        return run
