"""The Mamba-2 / grouped-query hybrid's serving cells WITH routed
experts (granite-4.0-h-small): the granite driver's engine and the
sparse-expert driver's step log around a `ServingEngine` that serves a
`GraniteHybridConfig` with `n_experts` > 0, and a check of its own.

The loop, the window, the traced slice's early profiler start, the
per-step `cache_bytes_per_slot` reading, the topped-up sample and the
engine's construction from the configuration's `shape` and `engine`
group are `drivers/serve_engine_granite_hybrid.py`'s; the per-step
`moe_experts_hit` log is `drivers/serve_engine_afmoe.py`'s, unchanged.
What this driver brings:

  * the model's operations (`model_flops`, what `mfu.serve` reads) are
    counted with `lib/costs_granite_moe_hybrid.py`: a token is ROUTED
    through the shared MLP, the router and top_k x held / n_experts of
    this chip's experts in every layer;
  * the (token, choice) pairs logged beside each decode step's
    `moe_experts_hit` (what `moe_expert_roofline` divides by) are the
    pairs that reach the experts HELD here, top_k x held / n_experts of
    a token's in every layer: the expected share;
  * the notes carry this family's counters (`moe_experts_hit` a layer
    and step, `moe_rows_max`, `state_slots_reset`, `cache_bytes_in_use`
    by kind: `full` and `state`) and `chunk_step_share_pct`;
  * `check()` judges the sampled greedy requests against the float32
    reference's one pass over each, three numbers with limits:
      - the program's LOGITS through its caches: the sampled requests,
        one a slot, prefilled chunk by chunk and then decoded token by
        token on the tokens they were served, by the family's own
        `paged_prefill_chunk` and `paged_decode_step` with the engine's
        kernels; `logit_err_median` is the median over the judged
        positions of a position's logits' distance from the
        reference's, relative to their spread;
      - the SERVED tokens, the engine's own output, against those
        logits: `served_not_top2_share`, the share of the served tokens
        that are neither the first nor the second of the judged logits.
        Where the engine served what its program computes it is 0: the
        check's step is another compilation of the same arithmetic, and
        where it sums a product in another order a near-tie may swap the
        first two (1 served token of 3,984 on one seed of this cell on a
        TPU v5e, PERF.md section 6). It ties the timed path to the
        logits judged above; a garbled stream fails it. The served tokens' gaps below
        the reference's best (`flip_gap_mean_sq`, `gap_mean`, the other
        serving cells' numbers) are logged, not compared: at these
        weights they grow with the seed's near-ties, and the int8
        control's overlap the program's;
      - the program's recurrent STATE after the last judged token,
        against the reference's: `state_err_first_layer_max`, the
        largest relative distance of one head's state in the first
        Mamba-2 layer. Its inputs are the embedding rows of the same
        tokens in both, so the program's own departure there is the
        rounding of its in-projection, which a decaying sum does not
        grow; a state kept in a lower precision is rounded every token,
        and a head that remembers ~1,000 tokens sums those roundings
        (PERF.md section 6: on random tokens a bfloat16 state moves the
        logits far less than the program's bf16 compute does; on the
        repetitive greedy streams of these weights it swamps the slow
        heads' sums).
    A control (`check(control=...)`) stands the reference computed in
    the control's way where the program was: its logits (the served
    tokens ranked in them) and its state.
"""

from __future__ import annotations

import numpy as np

from lib import costs_granite_moe_hybrid as costs

from drivers import serve_engine as base
from drivers import serve_engine_afmoe as afmoe
from drivers import serve_engine_granite_hybrid as granite

class Driver(granite.Driver, afmoe.Driver):
    def __init__(self, ctx):
        granite.Driver.__init__(self, ctx)
        # a program whose family has no routed experts (a parent commit)
        # fails here, at once, before any weight is made
        from paddle_tpu.models.granite_hybrid import GraniteHybridConfig

        GraniteHybridConfig(**self.shape)
        self.moe_steps = []
        self.pairs_a_token = (int(self.shape["top_k"])
                              * costs.held_share(self.shape)
                              * costs.layer_counts(self.shape)["expert"])
        self._refs = self._prog = self._tokens = None

    def window(self):
        self.submitted, self.cache_stat, self.moe_steps = [], [], []
        self.kernel = self.eng.paged_kernel
        self.slots = int(self.eng.max_slots)
        self.chunk_tokens = int(self.eng.prefill_chunk_tokens
                                or self.max_len)
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
            "state_slots_reset": m.state_slots_reset,
            "cache_bytes_in_use": m.cache_bytes_in_use,
            "kv_blocks_in_use_of": [int(m.kv_blocks_in_use),
                                    int(m.kv_blocks_total)],
        }
        run["notes"].update(notes)
        self.ctx.log("granite moe hybrid: " + repr(notes))
        return run

    # ------------------------------------------------------------------
    def _reference(self, sample):
        """The float32 reference's rows and states, a request at a time;
        kept for every check of this run on the same tokens (the
        program's and each control's judge against the same)."""
        tokens = [(np.asarray(r.spec["prompt"]).tobytes(),
                   np.asarray(r.tokens).tobytes()) for r in sample]
        if tokens != self._tokens:
            self._tokens, self._refs, self._prog = tokens, None, None
        if self._refs is None:
            pad_to = int(self.w["check"]["pad_to"])
            self._refs = [self.ref.reference_pass(
                self.params, self.shape, r.spec["prompt"], r.tokens, pad_to)
                for r in sample]
        return self._refs

    def _steps(self, cfg):
        """The check's prefill chunk and decode step: the family's own
        entries with the engine's kernels, each followed by the logits'
        distance from the reference's rows at the same positions and
        the `pick_rank` of the token served after them."""
        import jax

        from paddle_tpu.models import granite_hybrid as gh

        err, rank, kernel = self.ref.logit_err, self.ref.pick_rank, self.kernel
        scaling = np.float32(self.shape["logits_scaling"])

        def chunk(params, cache, tokens, start, rows, true_len, x, nxt):
            logits, cache = gh.paged_prefill_chunk(
                params, cache, tokens, start, rows, cfg,
                true_len=true_len, kernel=kernel)
            return (cache, err(logits[None], x[None], params["embed"],
                               scaling)[0], rank(logits[None], nxt[None])[0])

        def step(params, cache, tokens, pos, tables, xs, which, rows, nxt):
            out = gh.paged_decode_step(params, tokens, pos, tables,
                                       cache, cfg, kernel=kernel)
            x = xs[which, rows]
            return (out[1], err(out[0], x, params["embed"], scaling),
                    rank(out[0], nxt))

        return (jax.jit(chunk, donate_argnums=1),
                jax.jit(step, donate_argnums=1))

    def _program(self, sample, refs):
        """The sampled requests through the program's caches, one a
        slot: each prompt prefilled in the engine's chunks, then every
        slot decoded together on the tokens it was served, a slot that
        is done parked (it writes nothing and its state stays as it
        is). The decode step has the engine's slots (more where the
        sample holds more requests), the others parked: the timed
        step's shapes, so that its products sum as the timed step's do
        (PERF.md section 6). -> (per request the logits' `logit_err` at
        its judged positions, float64 [n1]; per request the served
        tokens' `pick_rank` in them, [n1]; per request every
        Mamba-2 layer's state after its last judged token)."""
        if self._prog is not None:
            return self._prog
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models import granite_hybrid as gh

        cfg = gh.GraniteHybridConfig(
            max_len=self.max_len, dtype=jnp.dtype(self.ctx.config["dtype"]),
            **self.shape)
        chunk, step = self._steps(cfg)
        S, Bt, C = len(sample), self.block_tokens, self.chunk_tokens
        slots = max(S, self.slots)
        maxb = -(-self.max_len // Bt)
        cache = gh.init_cache(cfg, S * maxb, Bt, slots)
        tables = np.zeros((slots, maxb), np.int32)
        tables[:S] = np.arange(S * maxb, dtype=np.int32).reshape(S, maxb)
        which = np.minimum(np.arange(slots), S - 1).astype(np.int32)
        xs = jnp.stack([x for x, _ in refs])
        last = []
        for i, r in enumerate(sample):
            prompt = np.asarray(r.spec["prompt"], np.int32)
            rows = np.zeros((2, maxb), np.int32)
            rows[0], rows[1, 0] = tables[i], i
            for a in range(0, len(prompt), C):
                n = min(C, len(prompt) - a)
                toks = np.zeros(C, np.int32)
                toks[:n] = prompt[a:a + n]
                cache, e, g = chunk(self.params, cache, toks, np.int32(a),
                                    rows, np.int32(n),
                                    refs[i][0][len(prompt) - 1],
                                    np.int32(r.tokens[0]))
            last.append((e, g))
        n0 = np.array([len(r.spec["prompt"]) for r in sample])
        n1 = np.array([len(r.tokens) for r in sample])
        errs, picks = [], []
        for j in range(int(n1.max()) - 1):
            on = np.zeros(slots, bool)
            on[:S] = j < n1 - 1
            toks = np.zeros(slots, np.int32)
            pos = np.full(slots, maxb * Bt, np.int32)
            rows, nxt = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
            for i, r in enumerate(sample):
                if on[i]:
                    toks[i], pos[i] = r.tokens[j], n0[i] + j
                    rows[i], nxt[i] = n0[i] + j, r.tokens[j + 1]
            cache, e, g = step(self.params, cache, toks, pos, tables, xs,
                               which, rows, nxt)
            errs.append(e[:S])
            picks.append(g[:S])
        errs = (np.asarray(jnp.stack(errs), np.float64) if errs
                else np.zeros((0, S)))
        picks = (np.asarray(jnp.stack(picks)) if picks
                 else np.zeros((0, S), np.int32))
        states = [[np.asarray(st["s"][i]) for st in cache["ssm"]]
                  for i in range(S)]
        self._prog = (
            [np.concatenate([[float(last[i][0])], errs[:n1[i] - 1, i]])
             for i in range(S)],
            [np.concatenate([[int(last[i][1])], picks[:n1[i] - 1, i]])
             for i in range(S)], states)
        return self._prog

    def check(self, control=None):
        chk, ref = self.w["check"], self.ref
        sample = self.sample()
        refs = self._reference(sample)
        if control is None:
            errs, picks, states = self._program(sample, refs)
        gaps, logit, served, heads = [], [], [], []
        for i, r in enumerate(sample):
            prompt, x = r.spec["prompt"], refs[i][0]
            if control is None:
                judged = ref.judge(self.params, self.shape, prompt, r.tokens,
                                   x)
                logit.append(errs[i])
                served.append(picks[i])
                got = states[i]
            else:
                xq, got = ref.reference_pass(
                    self.params, self.shape, prompt, r.tokens,
                    int(chk["pad_to"]), control=control)
                judged = ref.judge(self.params, self.shape, prompt, r.tokens,
                                   x, xq, control)
                logit.append(judged["logit_err"])
                served.append(judged["pick_rank"])
                del xq
            gaps.append(judged["gaps"])
            heads.append([ref.state_err(a, b, self.shape["mamba_head_dim"])
                          for a, b in zip(got, refs[i][1])])
        gaps = np.concatenate(gaps) if gaps else np.zeros(0)
        logit = np.concatenate(logit) if logit else np.zeros(0)
        served = np.concatenate(served) if served else np.full(1, 2)
        flips = gaps[gaps > 0]
        flip_gap = float(flips.mean()) if len(flips) else 0.0
        first = max((float(h[0].max()) for h in heads), default=float("inf"))
        tokens = len(gaps)
        # read for PERF.md and limits.py, not compared: how far the
        # served tokens lie below the reference's best grows with the
        # seed's near-ties, for the program and the control alike
        self.widest_gap = float(gaps.max()) if tokens else 0.0
        self.gap_mean = float(gaps.mean()) if tokens else float("inf")
        values = {
            "served_not_top2_share": float((served >= 2).mean()),
            "logit_err_median": (float(np.median(logit)) if len(logit)
                                 else float("inf")),
            "state_err_first_layer_max": first,
        }
        compared = {k: {"value": v, "limit": chk[k + "_limit"]}
                    for k, v in values.items()}
        compared["tokens_compared"] = {"value": tokens,
                                       "limit": chk["min_tokens"]}
        self.ctx.log(
            "check%s: %d tokens of %d requests compared, %d not the "
            "reference's first; %r; the largest head's state distance "
            "by Mamba-2 layer %r"
            % ("" if control is None else " (control %s)" % control,
               tokens, len(sample), len(flips),
               dict(values, gap_mean=self.gap_mean,
                    flip_gap_mean_sq=flip_gap ** 2),
               [round(max(float(h[k].max()) for h in heads), 6)
                for k in range(len(heads[0]))] if heads else []))
        correct = (tokens >= chk["min_tokens"]
                   and all(v <= chk[k + "_limit"] for k, v in values.items()))
        return bool(correct), compared
