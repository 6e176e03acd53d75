"""Continuous-batching serving engine: paged KV block pool, prefix
reuse by block-table aliasing, chunked prefill, and ONE compiled decode
(or speculative-verify) step for many concurrent requests.

Serving throughput is workload shape — one request per batch underfills
the lanes and every new prompt length recompiles. This engine
reproduces Orca-style iteration-level scheduling (Yu et al., OSDI '22)
in JAX/XLA idiom: static shapes everywhere, slots instead of dynamic
allocation. On top of that base (PR 2), admission reuses and bounds
prefill work (PR 4), and the KV cache itself is paged (PR 7):

  * Paged KV block pool — the per-layer cache is a pool of fixed
    `kv_block_tokens`-token blocks ([NB, Bt, H, Dh]); each slot owns a
    block-table row mapping logical depth to physical blocks
    (PagedAttention, Kwon et al., SOSP '23; the reference's
    PoolAllocator.h/MemoryHandle pooled-allocator lineage). Admission
    RESERVES the request's worst case (ceil((T0+max_new)/Bt) blocks)
    so decode can never deadlock, but blocks are ALLOCATED on demand
    as the sequence grows, and retirement frees the allocated blocks
    plus the reserved-but-unreached tail — HBM residency and admission
    capacity scale with tokens actually resident, not
    MAX_SLOTS x max_len (the slab this replaces).
  * Prefix reuse = table aliasing — completed prompt prefixes publish
    their PHYSICAL block ids into the trie pool (prefix_cache.py,
    RadixAttention-style); a hit writes those ids into the new slot's
    table (ref-counted, zero-copy — no dynamic_update_slice copies).
    When the suffix must recompute a token inside a shared block (the
    maximal-reuse case: the whole prompt is cached but the last
    token's logits must be computed), the block is COPY-ON-WRITE
    privatised first, so a shared block is never written through.
  * Chunked prefill — the uncached suffix runs through
    models/transformer.paged_prefill_chunk in chunks of
    `prefill_chunk_tokens`, interleaved with batched decode steps
    (Sarathi-Serve, Agrawal et al., OSDI '24). Chunks pad to pow-2
    buckets, so distinct compiled prefill shapes stay O(log max_len).
  * One jitted decode step — advances all MAX_SLOTS slots at once with
    per-slot positions, temperatures, and sampling keys; cache buffers
    are donated. Traced exactly once per engine lifetime. The eight
    host side-band arrays (now including the block tables and budget
    limits) are device-resident between steps; the steady decode loop
    re-uploads a band only when a scheduler event dirties it (block
    tables change only every `kv_block_tokens` decodes, at the
    on-demand append).
  * Self-drafting speculative decoding — with `spec_draft_len` = K,
    each decode phase proposes K-1 draft tokens per slot by prompt
    lookup (the last bigram's previous continuation in
    prompt+generated context — "self-drafting": no draft model) and
    verifies the K-token window in ONE batched compiled step
    (models/transformer.paged_verify_step, traced exactly once). The
    acceptance rule emits the model's own tokens — greedy outputs are
    IDENTICAL to the plain decode path whatever the drafts were;
    drafts only change how many tokens one step emits. Sampled
    requests keep the fold_in(key, token_index) schedule (position i
    uses index counts+i), so sampling is spec-invariant too.
  * Iteration-level scheduling — ServingEngine.step() retires a slot
    the moment its request emits EOS or exhausts its budget and refills
    it from the FCFS queue on the SAME step; a saturated block pool
    QUEUES admissions (backpressure) instead of raising, and the next
    retirement's freed blocks admit them. A pending slot advances at
    most ONE chunk per step (chunks always interleave with decodes —
    the Sarathi policy); `max_prefills_per_step` additionally caps the
    TOTAL chunks across slots per step.
  * Request SLO (ISSUE 8) — `submit(deadline_at=)` carries an absolute
    latency budget enforced at every hop (pre-admission, prefill
    chunk, decode): past it the request finishes with the terminal
    verdict 'expired' (partial tokens kept) and the scheduler spends
    nothing further on it. `submit(resume_tokens=)` is token-level
    resume: tokens an earlier incarnation already emitted become
    prefill context (aliasing whatever the prefix pool holds), the
    sampling-key schedule continues at the resume index, and only the
    remainder is decoded — the fleet's hedged failover rides this to
    turn "restart from token zero" into "keep decoding". `cancel(rid)`
    claws back work the fleet hedged elsewhere (demotion).

Correctness bar (tested): greedy engine output per request is
token-identical to sequential models/transformer.generate() at every
slot count and admission order, for every cache path — cold miss,
aliased hit, copy-on-write, post-eviction re-admit — and with
speculative decoding on or off (spec changes WHEN tokens are produced,
never WHICH). Identity is at the TOKEN level: padded/chunked prefill
drifts from the unpadded oracle in the last ~2 float bits — reduction
order under masked padding, present since PR 2 — which never moves an
argmax in practice and is pinned by the fixed-seed drills. Sampled
requests use a per-request fold_in(key, token_index) schedule —
deterministic per request and independent of slot assignment and
spec_draft_len, but not the same key schedule as
generate(temperature>0).
"""

from __future__ import annotations

import collections
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed import fault_injection as _fi
from ..fluid.core.kernels_sequence import bucket_pow2
from ..models import transformer as tlm
from ..models.scopes import scope
from .adapters import AdapterPool
from .integrity import (_FP_RTOL, BlockFingerprints, IntegrityError,
                        ServingSentinel)
from .kv_blocks import KVBlockAllocator, WindowBlockTables
from .kv_store import make_block_record, payload_crc
from .metrics import ServingMetrics
from .prefix_cache import PrefixCache, chain_keys
from .quantization import dequantize_params, quantize_params

__all__ = ["ServingEngine", "ServingHandle", "EngineFailed",
           "IntegrityError"]

_BANDS = ("tok", "pos", "alive", "temps", "counts", "base_keys",
          "tables", "limits", "aidx", "eos")

# bands the compiled decode step ADVANCES on device (ISSUE 19): a
# host-side event that dirties any of these between dispatch and read
# (admission, retirement, cancel, expiry, spec acceptance) means the
# device copies no longer carry host truth — the async chain must
# break and re-upload. Everything else in _BANDS is host-truth only
# (the device never writes it), so uploading those mid-flight is safe.
_DEVICE_ADVANCED = frozenset(("tok", "pos", "alive", "counts"))
# the same bands (band_lint reads the literal above), in the order the
# decode program returns them and `_unpack` reads them
_ADVANCED_ORDER = ("tok", "pos", "alive", "counts")


class EngineFailed(RuntimeError):
    """The engine (or the fleet replica driving it) died with requests
    pending. Raised by `ServingHandle.result()` instead of blocking
    forever, and by `ServingEngine.step()` on every call after the
    failure (the compiled steps donate their cache buffers, so a step
    that died mid-dispatch leaves the cache unusable — the latch keeps
    a half-donated cache from being stepped again). `replica` names the
    failing replica when the engine serves inside a fleet."""

    def __init__(self, msg: str, replica=None):
        super().__init__(msg)
        self.replica = replica


class ServingHandle(object):
    """Per-request future: filled in by the engine as steps run.
    `result()` drives the owning engine until this request completes
    (single-threaded engines have no background loop to wait on).

    Token-level resume (ISSUE 8): a handle submitted with
    `resume_tokens` carries tokens ALREADY emitted by an earlier
    incarnation of the same request (journaled by the fleet). The
    engine prefills prompt + resume as context — aliasing whatever
    prefix the pool holds — and decodes only the remainder: decode
    steps are never re-spent on journaled tokens, and the sampling key
    schedule continues at token index `resume_len`, so outputs stay
    token-identical to an uninterrupted run. `tokens` holds only the
    NEWLY generated tokens; `result()` returns the full sequence."""

    def __init__(self, engine, rid, prompt, max_new_tokens, temperature,
                 eos_id, seed, publish_len, deadline_at=None,
                 resume_tokens=None, adapter=None, handoff=None):
        self._engine = engine
        self.rid = rid
        self.prompt = prompt  # np.int32 [T0] — the ORIGINAL prompt
        self.resume_tokens = np.asarray(
            resume_tokens if resume_tokens is not None else [], np.int32)
        self.resume_len = int(self.resume_tokens.shape[0])
        # prefill context: prompt plus everything already emitted
        self.full_prompt = (
            np.concatenate([prompt, self.resume_tokens])
            if self.resume_len else prompt)
        # budget REMAINING: max_new_tokens is the request's original
        # total; the resumed tokens are already spent
        self.total_new_tokens = int(max_new_tokens)
        self.max_new_tokens = int(max_new_tokens) - self.resume_len
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.seed = seed
        # publish boundary: how many leading prompt tokens may be
        # published back to the prefix pool (None = whole prompt)
        self.publish_len = publish_len
        # absolute time.monotonic() budget (None = no deadline): the
        # engine expires the request at the next queue hop past it
        self.deadline_at = deadline_at
        # LoRA-style adapter name (ISSUE 12; None = the base model /
        # zero adapter) — resolved to a pool slot at admission
        self.adapter = adapter
        # durable-KV handoff package (ISSUE 16): the finished prefix's
        # serialized block records shipped by the fleet at migration/
        # failover. Consumed at admission — each record is token- and
        # fingerprint-verified before it enters the pool; outcome lands
        # in handoff_imported/handoff_fallback for the journal's done
        # side-band (the J011 fence)
        self.handoff = handoff
        self.handoff_imported = 0       # tokens imported clean
        self.handoff_fallback = False   # any re-prefill shortfall
        self.handoff_outcome = None     # set once the package is judged
        self.tokens: List[int] = []  # generated tokens (may include eos)
        self.done = False
        # 'eos' | 'budget' | 'expired' | 'cancelled'
        self.finish_reason: Optional[str] = None
        # set by ServingEngine.abort() when the engine dies with this
        # request pending: result() raises it instead of spinning on a
        # dead engine forever (ISSUE 6 satellite)
        self.error: Optional[BaseException] = None
        self.submit_t = time.monotonic()
        self.queue_wait_s: Optional[float] = None
        self.ttft_s: Optional[float] = None

    def result(self) -> np.ndarray:
        """Block (by stepping the engine) until done; returns the full
        sequence — prompt, then resumed tokens (if any), then this
        incarnation's generated tokens. An 'expired' verdict still
        returns (the partial sequence): at the engine level the
        deadline outcome is `finish_reason`, not an exception — the
        fleet layer turns it into `DeadlineExceeded` for its callers.
        Raises `EngineFailed` (naming the failing replica when the
        engine serves in a fleet) if the engine died with this request
        pending — including when a BACKGROUND thread owned the engine
        and crashed: the failure is propagated into the handle, never
        an indefinite block."""
        while not self.done:
            if self.error is not None:
                raise self.error
            if not self._engine.step():
                raise RuntimeError(
                    "engine made no progress but request %r is not done"
                    % self.rid
                )
        return np.concatenate(
            [self.full_prompt, np.asarray(self.tokens, np.int32)]
        )


class ServingEngine(object):
    """Continuous-batching engine over a transformer LM's paged decode
    primitives. Knobs: `max_slots` (concurrent requests in the batched
    decode), `max_len` (per-request position cap, bounded by the
    positional table), `min_bucket` (smallest prefill pad length),
    `max_prefills_per_step` (total prefill chunks per step across
    slots; each pending slot advances at most one chunk per step
    regardless, so None = all pending slots advance, 1 = only the FCFS
    head — latency-biased for in-flight decodes),
    `prefill_chunk_tokens` (max tokens per prefill chunk;
    None = whole suffix in one chunk), `kv_block_tokens` (KV pool
    block granularity — allocation, prefix caching, and copy-on-write
    all happen in whole blocks), `kv_pool_blocks` (physical blocks in
    the pool = the engine's KV HBM budget / (Bt tokens x layers);
    default max_slots x ceil(max_len/Bt), the slab-parity worst case),
    `spec_draft_len` (speculative window size K: the pending token
    plus K-1 self-drafted tokens verified per step; None/<2 = off),
    and `prefix_cache_tokens` (token budget of the shared prefix trie;
    None/0 disables reuse). `prefix_block_tokens` is the pre-paging
    name for the block granularity and still accepted: trie blocks ARE
    pool blocks now, so the two sizes cannot differ. `weights_version`
    tags the engine — and every token it emits — with the weight
    version its params came from (the fleet's live-rollout version
    fence; a weight swap is a new engine, never an in-place mutation).
    `paged_kernel` picks how the compiled steps attend over the block
    pool (ISSUE 13): "fused" = the Pallas kernels that walk the block
    table inside the kernel (parallel/paged_attention.py — no
    per-layer gathered view; the default on accelerator backends),
    "gather" = the XLA `_paged_view` form (the CPU-backend default,
    where fused would run interpreted). Greedy outputs are token-identical
    either way (tests/test_paged_kernel.py pins it per primitive and
    end-to-end).

    `kv_quant` (ISSUE 14) picks the KV pool's STORAGE dtype:
    "none" (the default — cache structure and traces byte-identical
    to the pre-quant engine), "int8", or "fp8" (float8_e4m3fn). At
    block granularity: each physical block carries a per-head f32
    absmax scale (side-bands on the cache pytree, keyed by physical
    block id), committed when the block is first filled — so prefix
    ALIASING shares the scale with the payload for free, COW copies
    both in one compiled op, and eviction/reuse recommits on the next
    fill. Writes quantize at the scatter inside the one compiled
    step; reads dequantize inside the fused Pallas kernels (scales as
    scalar-prefetch operands — no HBM-materialised dequantized view)
    or on the gather view on CPU. int8/fp8 holds ~4x the resident
    blocks per HBM byte at a fixed byte budget; `bench.py
    serving_quant` pins the greedy-agreement quality gate. NOT
    token-identical to f32 — a quantized engine is a different model
    by design, which is why a fleet refuses mixed kv_quant replicas.
    `weight_quant` ("int8" | None) additionally stores the params as
    per-tensor int8 + f32 scales (serving/quantization.py), dequant
    folded into the compiled steps — the decode HBM roofline's weight
    term drops ~4x independently of the KV side.

    Model families: the engine asks `cfg.serving`, the
    family's seam (a `models/parts.Seam`), for the cache and for the
    bodies of its two compiled steps — same scheduler, allocator,
    side-bands, sampler, spans and program names whatever the family —
    and for the caches it keeps (`cfg.serving.caches`), and builds
    those and no others. A family is its own mixers, caches and steps
    over the shared parts of `models/parts.py`:

      models/transformer.SERVING      ("paged",): the GPT block, one
                                      block pool on one table
      models/sambay.SERVING           ("paged", "window", "state")
      models/granite_hybrid.SERVING   ("paged", "state"): Mamba-2 +
                                      grouped-query layers, a pool an
                                      attention layer on the ONE table
      models/afmoe.SERVING            ("paged", "window"): routed
                                      experts, window + full layers
      models/mla_moe.SERVING          ("paged",): routed experts,
                                      latent attention, ONE latent
                                      pool a layer on the one table

    "window": window-attention pools whose blocks are freed behind the
    window (`kv_blocks.WindowBlockTables`, inside
    `engine.alloc_blocks`; both tables then ride the steps as one
    band). "state": per-slot recurrent state in the cache pytree,
    zeroed at admission (`engine.state_reset`), counted by
    `state_slots_reset` and, by kind beside the pools, in
    `cache_bytes_in_use` / `cache_bytes_per_slot`; a prefill chunk is
    told its slot's index in a row beside its table row. The two are
    independent: a family may keep window tables without state
    (`models/afmoe.SERVING`, `("paged", "window")`: nothing is reset at
    admission). The options a family cannot honour
    (`cfg.serving.refused`: by the seam's default, every family but
    the GPT block refuses the prefix cache, the KV store, speculation,
    KV and weight quantization, adapters and fingerprints, which need
    its per-head K/V blocks; hand-off import at `submit`) raise a
    ValueError that names the option and gives the family's own reason
    (`cfg.serving.refusal`: a recurrent state that block aliasing
    cannot restore, blocks freed behind the window); nothing is
    silently ignored. A family whose decode step computes counters on
    the device (`cfg.serving.step_counters`, empty by default: the
    router's, for a family with routed experts) returns them beside
    the logits; they ride the step's one packed result into the
    metrics of the same names.

    One decode program, one decode loop, two depths (ISSUE 29): the
    plain one-token decode is ONE compiled program (`_make_decode`)
    whatever the family — it retires a slot that hit EOS or its
    budget on the device and hands the host ONE packed array (tokens,
    trap flags, the magnitude's bits, the four advanced bands), read
    once — driven by ONE loop (`_decode_phase`). `async_dispatch` is
    the loop's depth. True (what None resolves to wherever the engine
    can, ISSUE 28): the chip runs a step ahead of the host — step N+1
    is dispatched off step N's device-resident outputs before N's
    result is read; tokens leave the engine one `step()` after the
    step that computed them, the same tokens in the same order; a host
    event that touches a device-advanced band (admission, cancel,
    expiry) makes the engine read first and upload host truth
    (`decode_dispatched_ahead` / `decode_chain_breaks` in the metrics
    count both). False: dispatch and read in the same `step()` — what
    None resolves to under speculation (acceptance is a host decision
    after every verify) and nowhere else. The hybrid family runs ahead
    like the GPT block (ISSUE 30): its window tables are advanced at
    each dispatch for the position THAT step writes — the host
    mirror's, or one past it when the step is chained — so a release
    is never decided further ahead than the step being dispatched
    (`_dispatch_decode` says why that is safe).

    Serving integrity (ISSUE 15): `integrity_traps` (default True)
    folds a per-slot non-finite trap — logits + softmax-denominator
    reduction (`transformer.logits_trap`) — into the SAME compiled
    steps (no new traces; decode still compiles exactly once); a
    tripped slot raises `IntegrityError` INSTEAD of emitting a token,
    and the fleet routes that into quarantine + taint-aware resume.
    `kv_fingerprints` (default False) adds per-physical-block
    folded-f32 checksums: committed when a block closes (publish into
    the prefix trie), spot-verified when an aliased block is re-opened
    by a different request (which is also where failover resume
    re-attaches), dropped when the block frees — a flipped block
    cannot silently serve prefix-cache hits.
    `integrity_spike_factor` (default None = off) additionally watches
    the step's max-|logit| with the shared EWMA/hysteresis
    TripDetector core (utils/detector.py — the training sentinel's),
    catching wrong-but-finite magnitude excursions.
    """

    def __init__(self, params, cfg, max_slots=8, max_len=None,
                 min_bucket=8, max_prefills_per_step=None, donate=True,
                 prefill_chunk_tokens=None, prefix_cache_tokens=None,
                 prefix_block_tokens=None, kv_block_tokens=None,
                 kv_pool_blocks=None, spec_draft_len=None,
                 replica_id=None, fault_injector=None,
                 scheduler_hook=None, weights_version=None,
                 adapter_registry=None, adapter_slots=8,
                 adapter_rank=None, paged_kernel=None,
                 kv_quant="none", weight_quant=None,
                 integrity_traps=True, kv_fingerprints=False,
                 integrity_spike_factor=None, kv_store=None,
                 kv_store_warm=False, async_dispatch=None):
        self._params = params
        self._cfg = cfg
        # the model family's seam (ISSUE 27): its cache, the bodies of
        # its two compiled steps, the options it cannot honour. The GPT
        # block's is tlm.SERVING; a config of another family carries
        # its own as `cfg.serving`
        fam = self._family = getattr(cfg, "serving", None) or tlm.SERVING
        asked = {"prefix_cache_tokens": prefix_cache_tokens,
                 "kv_store": kv_store, "spec_draft_len": spec_draft_len,
                 "kv_quant": kv_quant != "none",
                 "weight_quant": weight_quant,
                 "adapter_registry": adapter_registry,
                 "kv_fingerprints": kv_fingerprints}
        for opt in fam.refused:
            if asked[opt]:
                # nothing silently ignored; the reason is the family's
                # own (`refusal`: recurrent state that block aliasing
                # cannot restore, window blocks freed behind the window)
                raise ValueError(
                    "%s is not supported for the %r model family (%s); "
                    "refused options: %s"
                    % (opt, fam.name, fam.refusal, ", ".join(fam.refused)))
        # deterministic-exploration seam (ISSUE 9): the fleet threads
        # its SchedulerHook through so a controlled scheduler can park
        # a replica at engine-step granularity too; None costs one
        # attribute test per step
        self._sched_hook = scheduler_hook
        if getattr(cfg, "moe_experts", 0):
            # the GPT block's Switch layer (parallel/moe.py) has a
            # capacity cutoff that couples rows: padded chunk rows
            # would compete with real rows for expert slots and
            # silently change real outputs — refuse loudly instead.
            # Routed experts are served by a family whose layer has no
            # capacity (parallel/routed_experts.py)
            raise ValueError(
                "the Switch layer of moe_experts > 0 has a capacity "
                "cutoff that couples rows: not bit-stable under "
                "padded/chunked prefill, so not served (a family "
                "built on parallel/routed_experts.py is)")
        S = int(max_slots)
        if S < 1:
            raise ValueError("max_slots must be >= 1")
        self.max_slots = S
        # the positional table bounds every position (same clamp as
        # generate: a gather past it would silently clamp, not error)
        L = int(max_len or cfg.max_len)
        if "pos" in params:
            L = min(L, int(params["pos"].shape[0]))
        self.max_len = L
        self.min_bucket = int(min_bucket)
        if max_prefills_per_step is not None and max_prefills_per_step < 1:
            raise ValueError("max_prefills_per_step must be >= 1 or None")
        self.max_prefills_per_step = max_prefills_per_step
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1 or None")
        self.prefill_chunk_tokens = prefill_chunk_tokens
        if (kv_block_tokens is not None and prefix_block_tokens is not None
                and int(kv_block_tokens) != int(prefix_block_tokens)):
            raise ValueError(
                "trie blocks ARE pool blocks: kv_block_tokens (%d) and "
                "prefix_block_tokens (%d) cannot differ"
                % (int(kv_block_tokens), int(prefix_block_tokens)))
        if kv_block_tokens is None:
            kv_block_tokens = prefix_block_tokens
        Bt = 16 if kv_block_tokens is None else int(kv_block_tokens)
        if Bt < 1:  # an explicit 0 must be loud, not a silent default
            raise ValueError("kv_block_tokens must be >= 1")
        self.kv_block_tokens = Bt
        self.blocks_per_slot = -(-L // Bt)  # ceil: table row width
        NB = (S * self.blocks_per_slot if kv_pool_blocks is None
              else int(kv_pool_blocks))
        if NB < 1:
            raise ValueError("kv_pool_blocks must be >= 1")
        # a pool smaller than one slot's max_len worst case is legal —
        # submit() rejects the individual requests that can never fit
        self.num_kv_blocks = NB
        if spec_draft_len is not None and int(spec_draft_len) < 0:
            raise ValueError("spec_draft_len must be >= 0 or None")
        # K < 2 means no drafts to verify — the plain decode step
        self.spec_draft_len = (
            int(spec_draft_len) if spec_draft_len and int(spec_draft_len) >= 2
            else None)
        # `async_dispatch` is the decode loop's depth. True keeps the
        # chip one decode step ahead of the host (ISSUE 28): step N+1
        # is enqueued off step N's device outputs BEFORE N's one packed
        # result is read, so emit, retirement and block bookkeeping for
        # N run under N+1's device time; emission then runs one step
        # behind. None (the default) = ahead wherever the engine can:
        # not under speculation (its acceptance is a host decision
        # after every verify); every family runs ahead (the hybrid
        # family's window tables advance for the position the
        # dispatched step writes). False = lock-step: a
        # token leaves the engine in the step() that computed it.
        if async_dispatch is None:
            async_dispatch = self.spec_draft_len is None
        self.async_dispatch = bool(async_dispatch)
        if self.spec_draft_len is not None and self.async_dispatch:
            # speculative acceptance is a HOST decision after every
            # verify: deferring its read would need acceptance folded
            # into the compiled step. Loud refusal instead of a
            # silently wrong schedule.
            raise ValueError(
                "spec_draft_len does not compose with "
                "async_dispatch=True: speculative acceptance is a host "
                "decision after every verify step — run spec with "
                "async_dispatch=None or False")
        # paged-attention kernel selector (ISSUE 13): "fused" runs the
        # Pallas kernels that attend THROUGH the block table
        # (parallel/paged_attention.py — no per-layer gathered view);
        # "gather" keeps the XLA `_paged_view` form. Fixed for the
        # engine's lifetime (it is baked into the compiled steps);
        # resolution: explicit arg > backend default. The oracle suite
        # (tests/test_paged_kernel.py) is green, so the default IS
        # flipped to "fused" — on accelerator
        # backends, where the kernel compiles to Mosaic. The CPU
        # backend keeps "gather": there the fused path runs the
        # identical kernel INTERPRETED (resolve_interpret), ~4x slower
        # per step and ~1.5x per compile — correct but the wrong
        # default for a CI backend; the paged-kernel suite and the
        # serving_paged_kernel bench force "fused" explicitly on CPU.
        pk = paged_kernel or (
            "gather" if jax.default_backend() == "cpu" else "fused")
        if pk not in ("fused", "gather"):
            raise ValueError(
                "paged_kernel must be 'fused' or 'gather' (got %r)"
                % (pk,))
        self.paged_kernel = pk
        tlm._kv_quant_check(kv_quant)
        # per-block KV quantization (ISSUE 14): the pool's storage
        # dtype, fixed for the engine's lifetime (baked into the cache
        # pytree AND the compiled steps). 'none' keeps the exact
        # pre-quant cache structure and traces, so the default engine
        # stays token-identical to the PR 13 tree.
        self.kv_quant = kv_quant
        # per-tensor int8 weights (ISSUE 14): quantized ONCE below;
        # dequant is the first op of every compiled step
        if weight_quant not in (None, "int8"):
            raise ValueError(
                "weight_quant must be None or 'int8' (got %r)"
                % (weight_quant,))
        self.weight_quant = weight_quant
        # serving integrity (ISSUE 15): in-step numeric traps (per-slot
        # non-finite flag + max-|logit| scalar folded into the one
        # compiled step — no new traces; a tripped slot becomes an
        # IntegrityError instead of an emitted token), optional
        # per-block KV fingerprints (committed at publish, spot-
        # verified on aliased re-open — which is also where failover
        # resume re-attaches), and an opt-in EWMA magnitude spike
        # detector sharing the training sentinel's TripDetector core
        self.integrity_traps = bool(integrity_traps)
        if integrity_spike_factor is not None \
                and float(integrity_spike_factor) <= 1.0:
            raise ValueError(
                "integrity_spike_factor must be > 1 or None")
        if integrity_spike_factor is not None \
                and not self.integrity_traps:
            # the spike detector observes the max-|logit| scalar the
            # TRAP reduction computes — without traps it would be
            # silently dead, which is worse than a loud refusal
            raise ValueError(
                "integrity_spike_factor needs integrity_traps=True "
                "(the spike detector observes the trap reduction's "
                "magnitude scalar)")
        self._sentinel = ServingSentinel(
            spike_factor=integrity_spike_factor)  # guarded-by: scheduler
        if kv_fingerprints and not prefix_cache_tokens:
            # fingerprints commit at trie PUBLISH and verify at
            # aliased re-open — without a prefix cache neither point
            # exists, and the protection would be silently dead (all
            # counters zero forever while the operator believes
            # flipped blocks are covered): refuse loudly instead
            raise ValueError(
                "kv_fingerprints needs the prefix cache (pass "
                "prefix_cache_tokens=): fingerprints commit at trie "
                "publish and verify at aliased re-open — with no "
                "cache neither audit point ever runs")
        self._fp: Optional[BlockFingerprints] = (
            BlockFingerprints() if kv_fingerprints else None)  # guarded-by: scheduler
        self._fp_fn = None  # lazy-jitted fingerprint reduction
        self.metrics = ServingMetrics(S)
        self.metrics.paged_kernel = pk
        self.metrics.kv_quant = kv_quant
        self.metrics.weight_quant = weight_quant
        self.metrics.block_fp = self._fp
        self.metrics.kv_blocks_total = NB
        # live-rollout version fence (ISSUE 11): the weight version
        # these params came from — fixed for the engine's lifetime (a
        # weight swap is a NEW engine under a fresh incarnation, never
        # an in-place mutation), so every token this engine emits is
        # attributable to exactly one version
        self.weights_version = (
            None if weights_version is None else int(weights_version))
        self.metrics.weights_version = self.weights_version
        # one block's HBM cost, honest about the storage dtype (the
        # README sizing rule's block_bytes, surfaced through the
        # allocator's stats) — tlm.kv_block_bytes is the ONE formula,
        # shared with bench.py's byte-budget sizing and
        # bench_offline's roofline
        # the caches a family declares beside the paged pool (ISSUE
        # 27, 31): window layers' tables, whose blocks are freed behind
        # the window, and per-slot recurrent state, which lives in the
        # cache pytree and is zeroed at admission. Each is built and
        # handled only for a family that has it
        self._win: Optional[WindowBlockTables] = None  # guarded-by: scheduler
        self._has_state = "state" in fam.caches
        # counters a family's decode step computes on the device (the
        # router's, for a family with experts): running stats of the
        # metrics under the same names, read off the packed result
        self._step_counters = fam.step_counters
        self._state_bytes_per_slot = 0
        self._state_reset_fn = None
        call_block = None  # a merged 3-D pool's block, K + V
        # a family that sizes its own caches (every family but the GPT
        # block's: merged pools, window pools, state, a latent pool)
        # reports their bytes by kind
        self._by_kind = fam.cache_bytes is not None
        if self._by_kind:
            sizes = fam.cache_bytes(cfg, Bt)
            block_bytes = sizes["full"]
            call_block = sizes["call_block"]
            self._state_bytes_per_slot = sizes.get("state", 0)
            if "window" in fam.caches:
                self._win = WindowBlockTables(
                    S, self.blocks_per_slot, Bt, cfg.window,
                    block_bytes=sizes["window"])
        else:
            block_bytes = tlm.kv_block_bytes(
                cfg.layers, cfg.heads, cfg.dim // cfg.heads, Bt, kv_quant,
                act_itemsize=jnp.dtype(cfg.dtype).itemsize)
        if pk == "fused":
            # the kernels keep every slot's block table (and, on a
            # quantized pool, the named blocks' scales) in
            # scalar memory: a geometry that cannot fit is refused
            # HERE, with the arithmetic, not by the first step's
            # compile. A merged pool's decode call keeps the tables as
            # they are beside `pos` and `first` (ISSUE 36); a block's
            # bytes tell the check that the pool is one
            from ..parallel.paged_attention import check_paged_smem

            check_paged_smem(S, self.blocks_per_slot, Bt, cfg.heads,
                             kv_quant != "none", block_bytes=call_block)
        self.kv_block_bytes = block_bytes
        self._alloc = KVBlockAllocator(NB, Bt,
                                       block_bytes=block_bytes)  # guarded-by: scheduler
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache_tokens:
            self.prefix_cache = PrefixCache(
                int(prefix_cache_tokens), block_tokens=Bt,
                # _decref_block, not the raw allocator decref: a block
                # the eviction actually FREES must drop its committed
                # fingerprint too, or a recycled id would be judged
                # against its previous tenant's checksum (ISSUE 15)
                on_evict=self._decref_block,
            )
            self.metrics.prefix_cache = self.prefix_cache

        # paged LoRA adapter pool (ISSUE 12): a per-engine device pool
        # of stacked A/B deltas gathered by the per-slot adapter-index
        # band inside the ONE compiled decode/verify/chunk step — N
        # tenants with N adapters retrace nothing; slot 0 is the zero
        # adapter (requests without an adapter are exact no-ops)
        self._adapter_pool: Optional[AdapterPool] = None  # guarded-by: scheduler
        if adapter_registry is not None:
            self._adapter_pool = AdapterPool(
                cfg, adapter_registry, adapter_slots,
                rank=adapter_rank)
            self.metrics.adapter_pool = self._adapter_pool

        self._cache = fam.init_cache(cfg, NB, Bt, S, kv_quant=kv_quant)
        if weight_quant is not None:
            # quantize ONCE; the f32 tree the caller handed in is
            # theirs (fleet CRC walks / rollout see full precision) —
            # the engine's resident copy is int8 + per-tensor scales
            self._params = quantize_params(self._params)
        self._deq = (dequantize_params if weight_quant is not None
                     else None)
        # host-side truth of the per-slot side-bands; device copies are
        # kept across steps and re-uploaded only when dirtied. All
        # scheduler state below is confined to the thread driving
        # step()/submit() (the engine has no background loop). A future
        # background method must declare its `# thread: <domain>` —
        # lock_lint then flags its mutations of scheduler state
        # (undeclared methods are assumed to run on the owning domain).
        self._tok = np.zeros(S, np.int32)     # guarded-by: scheduler
        self._pos = np.zeros(S, np.int32)     # guarded-by: scheduler
        self._alive = np.zeros(S, bool)       # guarded-by: scheduler
        self._temps = np.zeros(S, np.float32)  # guarded-by: scheduler
        self._counts = np.zeros(S, np.int32)  # guarded-by: scheduler
        self._base_keys = np.zeros((S, 2), np.uint32)  # guarded-by: scheduler
        # per-slot block table (logical depth -> physical block id; -1
        # = not yet allocated) and position limit (T0 + max_new: verify
        # rows at or past it park their writes)
        self._tables = np.full((S, self.blocks_per_slot), -1,
                               np.int32)      # guarded-by: scheduler
        self._limits = np.zeros(S, np.int32)  # guarded-by: scheduler
        # per-slot adapter-index band (ISSUE 12): which adapter-pool
        # slot each request's q/v deltas gather from (0 = zero adapter)
        self._aidx = np.zeros(S, np.int32)    # guarded-by: scheduler
        # per-slot EOS id band (ISSUE 19): -1 = no EOS configured. The
        # compiled decode step retires slots itself, so the EOS rule
        # lives on device too.
        self._eos = np.full(S, -1, np.int32)  # guarded-by: scheduler
        self._n_alloc = np.zeros(S, np.int32)  # table entries >= 0  # guarded-by: scheduler
        self._reserved_tail = np.zeros(S, np.int32)  # guarded-by: scheduler
        self._dev: Dict[str, Any] = {}        # guarded-by: scheduler
        self._dirty = set(_BANDS)             # guarded-by: scheduler
        self._slot_req: List[Optional[ServingHandle]] = [None] * S  # guarded-by: scheduler
        # per-slot chunked-prefill cursors + FCFS order of pending slots
        self._prefill_state: Dict[int, dict] = {}  # guarded-by: scheduler
        self._prefill_q: collections.deque = collections.deque()  # guarded-by: scheduler
        # per-slot self-drafting index (spec decode): the context token
        # list, a bigram -> end-of-last-occurrence map maintained
        # incrementally per emitted token, and the tail bigram's
        # PREVIOUS occurrence — O(1) per step instead of rescanning the
        # whole context every decode
        self._spec_ctx: Dict[int, dict] = {}  # guarded-by: scheduler

        self._queue: collections.deque = collections.deque()  # guarded-by: scheduler
        self._next_rid = 0                    # guarded-by: scheduler
        # any request ever carried a deadline -> the per-step expiry
        # sweep runs; stays False (zero hot-path cost) otherwise
        self._deadlines = False               # guarded-by: scheduler
        self._donate = bool(donate)
        self._chunk_fns: Dict[int, Any] = {}
        # exactly ONE decode program per engine lifetime, the same at
        # either depth of the loop: `_decode` to jax.jit and
        # "decode_step" to the trace counter
        self._decode_fn = self._make_decode()
        # the one in-flight dispatched-not-yet-read step (async
        # dispatch); the lock-step depth never leaves one pending
        self._inflight: Optional[dict] = None  # guarded-by: scheduler
        self._verify_fn = (
            self._make_verify() if self.spec_draft_len else None)
        self._cow_fn = None
        # failure latch (abort() docstring) + fleet attribution
        self.replica_id = replica_id
        self._failed: Optional[EngineFailed] = None  # guarded-by: scheduler
        # fault-injection tick source for step(): an explicit injector
        # (fleet drills give each replica its own), or — resolved
        # lazily on the first step — the process-wide default_injector
        # when PADDLE_FAULT is set, else an inert one (same contract as
        # the trainer CLI's per-batch tick; see fault_injection.py)
        self._injector = fault_injector       # guarded-by: scheduler
        # durable KV tier (ISSUE 16): a fleet-shared KVBlockStore the
        # engine WRITES closed blocks into at publish (self-describing
        # records: quantized codes + scale side-bands + the PR 15
        # fingerprint as the transfer checksum) and READS at admission
        # (handoff import) / construction (warm start). The store is
        # internally locked; the engine only ever touches it from the
        # scheduler thread.
        if kv_store is not None and int(kv_store.block_tokens) != Bt:
            raise ValueError(
                "kv_store block geometry mismatch: store has "
                "block_tokens=%d, engine has %d — records would never "
                "align with the trie chain keys"
                % (int(kv_store.block_tokens), Bt))
        if kv_store is not None and self.prefix_cache is None:
            # spill happens at trie PUBLISH and warm start targets the
            # trie — without a prefix cache neither path exists and the
            # store would be silently dead (same refusal shape as
            # kv_fingerprints above)
            raise ValueError(
                "kv_store needs the prefix cache (pass "
                "prefix_cache_tokens=): blocks spill at trie publish "
                "and warm-start restores into the trie")
        self._kv_store = kv_store             # thread: shared (store locks itself)
        self.metrics.kv_store = kv_store
        if kv_store is not None and kv_store_warm:
            # warm the trie from the store BEFORE traffic: a restarted
            # or autoscaled replica serves its first shared-prefix hit
            # without re-decoding the prefix
            self.warm_from_store()

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------
    def _make_decode(self):
        """The engine's one plain decode program (ISSUE 29): one token
        per live slot through the model family's decode step — the
        paged scatter write + attention, the greedy/sampled next token
        on the `fold_in(base_key, count)` schedule, and the ISSUE 15
        numeric traps (per-slot non-finite flag + max-|logit| scalar,
        FOLDED into the same trace; off = constant zeros, no reduction
        in the graph) — followed by the device-side retirement rule
        (`tlm.decode_retire`): a slot hitting EOS or budget emits that
        final token and parks — its next scatter write resolves to the
        out-of-range sentinel block and its emitted lane carries -1
        padding the host discards — so a step chained off this one's
        outputs needs no host decision.

        Everything the host reads of the step comes back in ONE int32
        array (ISSUE 28; `_unpack` is its inverse): the S emitted
        tokens, the S trap flags and the magnitude scalar's bits, then
        the four advanced bands, so the host holds its mirrors to the
        device's without a transfer of their own. Traced exactly once
        per engine lifetime, as `_decode` to jax.jit and "decode_step"
        to the trace counter."""
        cfg, fam = self._cfg, self._family
        metrics, deq = self.metrics, self._deq
        Lv = self.blocks_per_slot * self.kv_block_tokens
        kernel = self.paged_kernel  # baked into the one compiled step
        kv_quant = self.kv_quant    # ditto: storage dtype is traced in
        traps = self.integrity_traps  # baked in: trap reduction or not

        def _decode(params, cache, tables, tok, pos, alive, temps,
                    counts, base_keys, limits, eos, adapters=None,
                    aidx=None):
            metrics.count_trace("decode_step")  # trace-time side effect
            if deq is not None:  # int8 weights upcast INSIDE the step
                params = deq(params)
            # dead slots park their write past the table span: the
            # block lookup resolves them to the out-of-range sentinel
            # block and the scatter DROPS the row, so a retired slot
            # can never dirty a block a future request will claim
            write_pos = jnp.where(alive, pos, jnp.int32(Lv))
            logits, cache, *stats = fam.decode_step(
                params, tok, write_pos, tables, cache, cfg,
                adapters=adapters, adapter_idx=aidx, kernel=kernel,
                kv_quant=kv_quant,
            )
            # the step's tail under its device scopes (models/scopes.py)
            with scope("step_sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                keys = jax.vmap(jax.random.fold_in)(base_keys, counts)
                safe_t = jnp.where(temps > 0, temps, 1.0)
                sampled = jax.vmap(
                    lambda k, l, t: jax.random.categorical(
                        k, l.astype(jnp.float32) / t
                    )
                )(keys, logits, safe_t).astype(jnp.int32)
                nxt = jnp.where(temps > 0, sampled, greedy)
            with scope("step_traps"):
                if traps:
                    trap = tlm.logits_trap(logits) & alive
                    scale = tlm.logit_amax(logits, alive)
                else:
                    trap = jnp.zeros_like(alive)
                    scale = jnp.float32(0.0)
            with scope("step_retire"):
                # dead lanes emit -1 padding; a live lane emits its
                # token even on its retirement step (EOS/budget tokens
                # ARE emitted, exactly like the host _emit rule)
                emitted = jnp.where(alive, nxt, jnp.int32(-1))
                nalive, npos = tlm.decode_retire(alive, nxt, pos, limits,
                                                 eos)
                ntok = jnp.where(alive, nxt, tok)
                row = jnp.concatenate([
                    emitted, trap.astype(jnp.int32),
                    jax.lax.bitcast_convert_type(
                        scale.astype(jnp.float32), jnp.int32)[None]])
                ncounts = counts + alive.astype(jnp.int32)
                # a family's own step counters (`step_counters`, int32,
                # one a name) ride the same array, last
                packed = jnp.concatenate([
                    row, ntok, npos, nalive.astype(jnp.int32), ncounts]
                    + [st.astype(jnp.int32) for st in stats])
            return cache, ntok, npos, nalive, ncounts, packed

        kw = {"donate_argnums": (1,)} if self._donate else {}
        return jax.jit(_decode, **kw)

    def _unpack(self, packed):
        """The host's view of a decode step's packed result ->
        (tokens [S], trap flags [S], the magnitude, the bands (tok,
        pos, alive, counts) as the step left them, the family's step
        counters). The one blocking device-to-host read of a decode
        step."""
        S = self.max_slots
        flat = np.asarray(packed)
        tok, pos, alive, counts = flat[2 * S + 1:6 * S + 1].reshape(4, S)
        return (flat[:S], flat[S:2 * S].astype(bool),
                float(flat[2 * S:2 * S + 1].view(np.float32)[0]),
                (tok, pos, alive.astype(bool), counts), flat[6 * S + 1:])

    def _make_verify(self):
        """ONE compiled speculative-verify step: writes every slot's
        K-token window into its paged cache, returns the model's
        candidate token after each window prefix. Host-side acceptance
        turns candidates into emitted tokens; device-side this is a
        fixed [S, K] shape traced exactly once per engine lifetime."""
        cfg, metrics = self._cfg, self.metrics
        K = self.spec_draft_len
        Lv = self.blocks_per_slot * self.kv_block_tokens
        kernel = self.paged_kernel  # baked into the one compiled step
        kv_quant = self.kv_quant
        deq = self._deq
        traps = self.integrity_traps

        def _verify(params, cache, tables, window, pos, alive, limits,
                    temps, counts, base_keys, adapters=None, aidx=None):
            metrics.count_trace("spec_verify")  # trace-time side effect
            if deq is not None:
                params = deq(params)
            rows = pos[:, None] + jnp.arange(K)[None, :]  # [S, K]
            # dead slots and rows past the request's token budget park
            ok = alive[:, None] & (rows < limits[:, None])
            wpos = jnp.where(ok, rows, jnp.int32(Lv))
            logits, cache = tlm.paged_verify_step(
                params, cache, window, pos, wpos, tables, cfg,
                adapters=adapters, adapter_idx=aidx, kernel=kernel,
                kv_quant=kv_quant,
            )
            with scope("step_sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # per-position sampling keys: position i of a slot
                # whose request has emitted `counts` tokens samples
                # token index counts + i — the SAME fold_in schedule
                # the plain decode path uses, so sampled outputs are
                # spec-invariant
                idx = counts[:, None] + jnp.arange(K)[None, :]
                keys = jax.vmap(
                    jax.vmap(jax.random.fold_in, in_axes=(None, 0)),
                    in_axes=(0, 0),
                )(base_keys, idx)
                safe_t = jnp.where(temps > 0, temps, 1.0)
                sampled = jax.vmap(
                    jax.vmap(
                        lambda k, l, t: jax.random.categorical(
                            k, l.astype(jnp.float32) / t
                        ),
                        in_axes=(0, 0, None),
                    ),
                    in_axes=(0, 0, 0),
                )(keys, logits, safe_t).astype(jnp.int32)
                cand = jnp.where((temps > 0)[:, None], sampled, greedy)
            # ISSUE 15 traps over the whole [S, K] window, reduced to
            # per-slot (any corrupt row in a slot's window trips it)
            with scope("step_traps"):
                if traps:
                    trap = tlm.logits_trap(logits).any(axis=-1) & alive
                    scale = tlm.logit_amax(logits, alive)
                else:
                    trap = jnp.zeros_like(alive)
                    scale = jnp.float32(0.0)
            return cache, cand, trap, scale

        kw = {"donate_argnums": (1,)} if self._donate else {}
        return jax.jit(_verify, **kw)

    def _chunk_fn(self, Cb):
        """One compiled prefill-chunk step per pow-2 bucket: extends a
        slot's cached prefix by a [Cb]-padded chunk and returns the
        would-be first generated token (meaningful only when the chunk
        completes the prompt)."""
        fn = self._chunk_fns.get(Cb)
        if fn is not None:
            return fn
        cfg, metrics, fam = self._cfg, self.metrics, self._family
        kernel = self.paged_kernel  # baked into the per-bucket step
        kv_quant = self.kv_quant
        deq = self._deq
        traps = self.integrity_traps

        def _chunk(params, cache, padded, start, table_row, true_len,
                   temp, key, adapters=None, aidx=None):
            metrics.count_trace("prefill_T%d" % Cb)
            if deq is not None:
                params = deq(params)
            logits, cache = fam.prefill_chunk(
                params, cache, padded, start, table_row, cfg,
                true_len=true_len, adapters=adapters, adapter_idx=aidx,
                kernel=kernel, kv_quant=kv_quant,
            )
            with scope("step_sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                sampled = jax.random.categorical(
                    key,
                    logits.astype(jnp.float32)
                    / jnp.where(temp > 0, temp, 1.0),
                ).astype(jnp.int32)
                first = jnp.where(temp > 0, sampled, greedy)
            # ISSUE 15 trap on the chunk's last-token logits. A NaN
            # written MID-chunk propagates: attention over a NaN K/V
            # row yields NaN logits at the final chunk, which is the
            # only chunk the host reads back anyway (mid-prompt chunks
            # stay dispatch-only so prefill keeps overlapping decode)
            with scope("step_traps"):
                if traps:
                    trap = tlm.logits_trap(logits)
                    scale = tlm.logit_amax(logits)
                else:
                    trap = jnp.bool_(False)
                    scale = jnp.float32(0.0)
            return cache, first, trap, scale

        kw = {"donate_argnums": (1,)} if self._donate else {}
        fn = jax.jit(_chunk, **kw)
        self._chunk_fns[Cb] = fn
        return fn

    def _make_cow(self):  # band-verb: cow
        """Copy-on-write: privatise one shared block before the suffix
        writes into it. ONE compiled shape total (fixed block size) —
        the only device copy left in the reuse path; plain aliasing
        moves zero bytes. On a quantized pool each layer dict also
        carries the k_scale/v_scale side-bands, row-indexed by the
        same physical block id — copying every band privatises
        payload AND scale in the same compiled op, so the private
        block dequantizes bit-identically to the shared one it
        forked from."""
        metrics = self.metrics

        def _cow(cache, dst, src):
            metrics.count_trace("cow_copy")
            return [
                {band: buf.at[dst].set(buf[src])
                 for band, buf in kv.items()}
                for kv in cache
            ]

        kw = {"donate_argnums": (0,)} if self._donate else {}
        return jax.jit(_cow, **kw)

    # ------------------------------------------------------------------
    # device-resident side-bands
    # ------------------------------------------------------------------
    def _band(self, name):
        if name in self._dirty:
            host = getattr(self, "_" + name)
            if name == "tables" and self._win is not None:
                # a family with window layers takes both tables as one band
                host = np.stack([host, self._win.tables])
            self._dev[name] = jnp.asarray(host)
            self._dirty.discard(name)
            self.metrics.band_uploads += 1
        return self._dev[name]

    def _bands(self, *names):
        """The device copies of several side-bands, in order; the
        dirty ones among them upload first, under ONE span (a steady
        decode loop uploads nothing and opens none)."""
        if self._dirty.intersection(names):
            with self.metrics.phase("engine.upload"):
                return [self._band(n) for n in names]
        return [self._dev[n] for n in names]

    def _mark_dirty(self, *names):
        self._dirty.update(names or _BANDS)

    def _adapter_args(self, aidx) -> dict:
        """Extra kwargs for the compiled steps when the adapter pool
        is on: the stacked pool arrays + the adapter-index side-band
        (`aidx` — the [S] device band for decode/verify, a scalar for
        a prefill chunk). Empty when adapters are off, so the traced
        graphs stay byte-identical to the pre-adapter engine."""
        if self._adapter_pool is None:
            return {}
        return {"adapters": self._adapter_pool.device_arrays(),
                "aidx": aidx}

    # ------------------------------------------------------------------
    # integrity (ISSUE 15)
    # ------------------------------------------------------------------
    def _trip(self, kind: str, detail: str):
        """Raise the integrity event: step()'s except path latches the
        engine and the fleet's _on_crash routes an IntegrityError into
        quarantine + taint-aware resume instead of plain failover."""
        raise IntegrityError(
            "integrity trip%s: %s" % (
                "" if self.replica_id is None
                else " (replica %s)" % self.replica_id,
                detail),
            kind=kind, replica=self.replica_id)

    def _check_integrity(self, trap, scale, where: str, slots=None):
        """Judge one compiled step's trap flag(s) + magnitude scalar.
        A tripped slot becomes an integrity event INSTEAD of an
        emitted token — the caller checks BEFORE its emit loop, so no
        token from a poisoned step ever reaches a handle (or, through
        the fleet, the journal)."""
        trap = np.atleast_1d(np.asarray(trap))
        verdict = self._sentinel.observe(bool(trap.any()), float(scale))
        if verdict != "ok":
            self._trip_verdict(verdict, trap, scale, where, slots)

    def _trip_verdict(self, verdict, trap, scale, where: str, slots=None):
        """Raise the sentinel's verdict on host values already read."""
        if verdict == "trap":
            bad = (slots if slots is not None
                   else [int(s) for s in np.nonzero(trap)[0]])
            rids = [self._slot_req[s].rid for s in bad
                    if self._slot_req[s] is not None]
            self._trip("trap",
                       "non-finite logits in %s step (slots %s, rids "
                       "%s)" % (where, bad, rids))
        self._trip("spike",
                   "logit magnitude spike in %s step (max-|logit| "
                   "%.3g vs EWMA %.3g x factor %g)"
                   % (where, float(scale),
                      self._sentinel.detector.ewma or 0.0,
                      self._sentinel.detector.spike_factor))

    def _fp_of(self, bid: int) -> float:
        """Recompute one physical block's fingerprint on device. The
        reduction is jitted ONCE (trace name "block_fp") — never
        donated: the cache must survive the read."""
        if self._fp_fn is None:
            metrics = self.metrics

            def _fp(cache, b):
                metrics.count_trace("block_fp")
                return tlm.paged_block_fingerprint(cache, b)

            self._fp_fn = jax.jit(_fp)
        return float(self._fp_fn(self._cache, jnp.int32(int(bid))))

    def _decref_block(self, bid) -> bool:
        """Drop one pool reference; a block actually FREED also drops
        its committed fingerprint (a recycled id must never be judged
        against the previous tenant's checksum). The ONE decref every
        engine-side release path uses (slot retirement, trie
        eviction)."""
        freed = self._alloc.decref(bid)
        if freed and self._fp is not None:
            self._fp.drop(int(bid))
        return freed

    def _flip_resident_block(self):
        """Consume a flip@ fault (ISSUE 15 drill): corrupt ONE resident
        physical block's K payload in place with finite garbage — the
        silent-data-corruption shape the numeric traps CANNOT see (no
        NaN) and only a fingerprint spot-check catches. Deterministic
        victim: the lowest in-use physical id. With nothing resident
        the fault re-arms for the next step, so flip@N on a
        still-empty pool lands on the first real block."""
        bid = next((b for b in range(self.num_kv_blocks)
                    if self._alloc.refcount(b) > 0), None)
        if bid is None:
            self._injector.rearm_flip()
            return
        kv = self._cache[0]
        buf = kv["k"]
        row = buf[bid]
        if buf.dtype == jnp.int8:
            garb = jnp.clip(row.astype(jnp.int32) + 37,
                            -127, 127).astype(jnp.int8)
        else:
            garb = (row.astype(jnp.float32) * -1.0
                    + 1.7).astype(buf.dtype)
        kv["k"] = buf.at[bid].set(garb)

    # ------------------------------------------------------------------
    # durable KV tier (ISSUE 16)
    # ------------------------------------------------------------------
    def _serialize_block(self, bid: int):  # band-verb: serialize
        """Flatten one physical block across every layer and band into
        (payload bytes, meta rows). Meta rows are ("li.band", dtype,
        shape-per-block) in the SAME sorted-band order
        `paged_block_fingerprint` folds, so a record is self-describing
        on a replica that never saw this pool: codes AND quant-scale
        side-bands travel together, and the fingerprint is recomputable
        from the payload alone."""
        parts = []
        meta = []
        b = int(bid)
        for li, kv in enumerate(self._cache):
            for band in sorted(kv):
                arr = np.asarray(kv[band][b])
                meta.append(("%d.%s" % (li, band), str(arr.dtype),
                             tuple(int(x) for x in arr.shape)))
                parts.append(arr.tobytes())
        return b"".join(parts), meta

    def _upload_block_record(self, rec, bid: int) -> bool:  # band-verb: import
        """Write one store record's payload into physical block `bid`
        (in-place band update, the `_flip_resident_block` idiom).
        Validates EVERY meta row against this engine's cache geometry
        before touching the device — False (and an untouched cache)
        on any layer/band/dtype/shape mismatch, so a foreign-geometry
        record can never half-write a block."""
        payload = rec["payload"]
        off = 0
        planned = []
        for name, dtype, shape in rec["meta"]:
            li_s, _, band = str(name).partition(".")
            try:
                li = int(li_s)
            except ValueError:
                return False
            if li < 0 or li >= len(self._cache) \
                    or band not in self._cache[li]:
                return False
            buf = self._cache[li][band]
            shape = tuple(int(x) for x in shape)
            if shape != tuple(buf.shape[1:]) or str(buf.dtype) != dtype:
                return False
            n = int(np.prod(shape, dtype=np.int64)) \
                * np.dtype(dtype).itemsize
            chunk = payload[off:off + n]
            if len(chunk) != n:
                return False
            off += n
            planned.append(
                (li, band, np.frombuffer(chunk, dtype).reshape(shape)))
        if off != len(payload):
            return False
        b = int(bid)
        for li, band, vals in planned:
            kv = self._cache[li]
            kv[band] = kv[band].at[b].set(jnp.asarray(vals))
        return True

    def _record_fp_ok(self, rec, fp_d) -> bool:
        """The handoff/warm transfer checksum: the RECOMPUTED on-device
        fingerprint of the uploaded block vs the record's committed one
        (same tolerance as the aliased re-open spot-check)."""
        exp = float(rec["fp"])
        return abs(float(fp_d) - exp) <= _FP_RTOL * max(1.0, abs(exp))

    def warm_from_store(self) -> int:  # band-verb: import
        """Restore the durable store's chains into THIS engine's prefix
        trie (restart / autoscale warm start): parent-before-child over
        the store snapshot, each block crc- and fingerprint-verified on
        upload, grafted under the trie with the fresh block's single
        pool ref TRANSFERRED to the trie (on_evict drops it). Corrupt
        entries are skipped and quarantined — with their whole subtree,
        a child's context is its ancestors' payloads — never served.
        Stops (rather than evicting warmed chains or starving traffic)
        at the trie token budget or pool exhaustion. Returns blocks
        restored."""
        store = self._kv_store
        pc = self.prefix_cache
        if store is None or pc is None:
            return 0
        Bt = self.kv_block_tokens
        n_warm = 0
        chain: Dict[int, list] = {}  # key -> tokens through this block
        skipped = set()
        for rec in store.iter_chains():
            key = rec["key"]
            par = rec["parent"]
            if par in skipped:
                skipped.add(key)  # corrupt ancestor: subtree is dead
                continue
            if par != 0 and par not in chain:
                continue  # unrooted (hole upstream): nothing to graft
            toks = (chain[par] if par else []) \
                + [int(t) for t in rec["tokens"]]
            depth = len(toks) // Bt
            m = pc.match(np.asarray(toks, np.int32), record=False)
            have = m.length
            m.release()
            if have >= depth * Bt:
                chain[key] = toks  # already resident (or just warmed)
                continue
            if pc.size_tokens + Bt > pc.token_budget:
                break  # budget: deeper warms would evict earlier ones
            if len(rec["payload"]) != rec["nbytes"] \
                    or payload_crc(rec["payload"]) != rec["crc"]:
                store.quarantine(key)
                self.metrics.store_quarantined += 1
                skipped.add(key)
                continue
            bid = self._alloc.try_alloc()
            if bid is None:
                break  # pool pressure: serve traffic over warmth
            ok = self._upload_block_record(rec, bid)
            fp_d = self._fp_of(bid) if ok else None
            if not ok or not self._record_fp_ok(rec, fp_d):
                self._decref_block(bid)
                store.quarantine(key)
                self.metrics.store_quarantined += 1
                skipped.add(key)
                continue
            if self._fp is not None:
                self._fp.commit(bid, fp_d)
            # ancestors are resident (the chain[] gate above), so only
            # this deepest block is novel to the publish
            pc.publish(np.asarray(toks, np.int32), depth,
                       lambda _d, b=bid: b)
            n_warm += 1
            self.metrics.store_warm_blocks += 1
            chain[key] = toks
        return n_warm

    # ------------------------------------------------------------------
    # block bookkeeping
    # ------------------------------------------------------------------
    def _blocks_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.kv_block_tokens)

    def _ensure_blocks(self, s: int, lo: int, hi: int):
        """Materialise (from this slot's reservation) every block
        covering positions [lo, hi) that the table has not allocated
        yet — the on-demand append that keeps residency at tokens
        actually written."""
        if hi <= lo:
            return
        Bt = self.kv_block_tokens
        for b in range(lo // Bt, (hi - 1) // Bt + 1):
            if self._tables[s, b] < 0:
                self._tables[s, b] = self._alloc.alloc_reserved()
                self._reserved_tail[s] -= 1
                self._n_alloc[s] += 1
                self._mark_dirty("tables")

    def _advance_window(self, spans):
        """The hybrid family's window tables (ISSUE 27), inside
        `engine.alloc_blocks`: for every (slot, lo, hi) about to write
        positions [lo, hi), free the blocks that fall wholly behind
        the window and materialise the ones the write needs."""
        m, win = self.metrics, self._win
        with m.phase("engine.window_release"):
            changed = [win.advance(int(s), int(lo), int(hi))
                       for s, lo, hi in spans]
            m.window_blocks_released = win.released_total
            if any(changed):
                self._mark_dirty("tables")

    def _reset_slot_state(self, s: int):
        """A request admitted to slot `s` starts from zero recurrent
        state (the slot's last tenant left its own) and, where the
        family has window layers, from an empty window table."""
        if self._win is not None and (self._win.tables[s] >= 0).any():
            raise RuntimeError("slot %d admitted over a live window table"
                               % s)
        if self._state_reset_fn is None:
            kw = {"donate_argnums": (0,)} if self._donate else {}
            self._state_reset_fn = jax.jit(self._family.reset_slot_state,
                                           **kw)
        self._cache = self._state_reset_fn(self._cache, jnp.int32(s))
        self.metrics.state_slots_reset += 1

    def _reclaim_for(self, need_new: int):
        """Evict idle trie chains until `need_new` blocks are
        available — but ONLY when eviction can actually bridge the gap
        (the freeable gain is trie payloads nobody holds whose pool
        refcount is 1: eviction of a slot-aliased or match-held block
        frees nothing). A failed admission attempt must leave the trie
        INTACT: a block-starved request retries every scheduler step,
        and unconditional reclaim would drain every shareable chain
        before anything admits (review hardening)."""
        pc = self.prefix_cache
        if pc is None or self._alloc.available >= need_new:
            return
        gain = sum(1 for bid in pc.idle_payloads()
                   if self._alloc.refcount(int(bid)) == 1)
        if self._alloc.available + gain < need_new:
            return  # hopeless right now: stay queued, trie untouched
        while self._alloc.available < need_new:
            # shareability yields to admitting the next request
            if pc.reclaim(need_new - self._alloc.available) == 0:
                break

    def _free_slot_blocks(self, s: int):
        """Retirement: drop this slot's reference on every allocated
        block (a block shared with the prefix trie or another slot
        survives) and release the reserved-but-unreached tail — the
        capacity an early-EOS request never grew into."""
        freed = 0
        for b in range(self.blocks_per_slot):
            bid = int(self._tables[s, b])
            if bid >= 0 and self._decref_block(bid):
                freed += 1
        tail = int(self._reserved_tail[s])
        if tail:
            self._alloc.release_reservation(tail)
        self.metrics.kv_blocks_freed_at_retire += freed
        self.metrics.kv_tail_blocks_freed += tail
        self._tables[s, :] = -1
        if self._win is not None:
            self._win.free(s)
        self._n_alloc[s] = 0
        self._reserved_tail[s] = 0
        self._limits[s] = 0
        self._mark_dirty("tables", "limits")

    # ------------------------------------------------------------------
    # scheduler
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens, temperature=0.0, eos_id=None,
               seed=0, publish_len=None, deadline_at=None,
               resume_tokens=None, adapter=None,
               handoff=None) -> ServingHandle:
        """Enqueue one request (FCFS). Returns a handle whose `.tokens`
        fills in as the engine steps; `handle.result()` drives the
        engine to completion of this request. Structurally impossible
        requests (past the positional table, or needing more blocks
        than the whole pool) raise; a merely SATURATED pool queues —
        the block-budget check happens at admission and retirements
        free capacity (backpressure, ISSUE 7 satellite). `publish_len`
        is the publish-boundary tag: at most this many leading prompt
        tokens are published to the prefix pool once prefill completes
        (None = the whole prompt; pass the shared-header length to keep
        request-unique tails out of the pool). `deadline_at` is an
        absolute time.monotonic() budget: past it the request is
        terminally 'expired' at the next queue hop (admission, prefill
        chunk, or decode) instead of consuming further steps.
        `resume_tokens` are tokens an earlier incarnation of this
        request already emitted (token-level resume, ISSUE 8): they
        become prefill context — prefix-aliased where the pool allows —
        and only `max_new_tokens - len(resume_tokens)` tokens are
        decoded, on the ORIGINAL request's sampling-key schedule.
        `handoff` is a durable-KV block package (ISSUE 16): the source
        replica's closed prompt blocks as kv_store records, imported at
        admission after per-block fingerprint verification — the clean
        path re-prefills ZERO closed-block tokens; any mismatch falls
        back to re-prefill (counted, never wrong)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        T0 = prompt.shape[0]
        if T0 < 1:
            raise ValueError("empty prompt")
        E = 0 if resume_tokens is None else len(resume_tokens)
        if int(max_new_tokens) - E < 1:
            raise ValueError(
                "max_new_tokens must leave >= 1 token past the resumed "
                "prefix (%d - %d resumed < 1)" % (int(max_new_tokens), E))
        if T0 + int(max_new_tokens) > self.max_len:
            raise ValueError(
                "request needs T0+max_new <= max_len (%d + %d > %d)"
                % (T0, int(max_new_tokens), self.max_len)
            )
        if self._blocks_for(T0 + int(max_new_tokens)) > self.num_kv_blocks:
            raise ValueError(
                "request worst case (%d blocks) exceeds the whole KV "
                "pool (%d blocks of %d tokens)"
                % (self._blocks_for(T0 + int(max_new_tokens)),
                   self.num_kv_blocks, self.kv_block_tokens)
            )
        if publish_len is not None and publish_len < 0:
            raise ValueError("publish_len must be >= 0 or None")
        if handoff and self._family is not tlm.SERVING:
            # imported K/V blocks are the GPT block's: they restore
            # neither a recurrent state nor a window table, nor fill a
            # latent pool — the family's own reason says which
            raise ValueError(
                "handoff import is not supported for the %r model family "
                "(%s)" % (self._family.name, self._family.refusal))
        if adapter is not None:
            # resolve-or-refuse NOW: an unknown adapter (or an engine
            # with no pool) must fail the caller synchronously, never
            # crash the scheduler at admission time
            if self._adapter_pool is None:
                raise ValueError(
                    "request names adapter %r but the engine has no "
                    "adapter pool (pass adapter_registry=)" % (adapter,))
            if not self._adapter_pool.registry.has(adapter):
                raise ValueError("unknown adapter %r (registered: %r)"
                                 % (adapter,
                                    self._adapter_pool.registry.names()))
        h = ServingHandle(self, self._next_rid, prompt, max_new_tokens,
                          temperature, eos_id, seed, publish_len,
                          deadline_at=deadline_at,
                          resume_tokens=resume_tokens, adapter=adapter,
                          handoff=handoff)
        self._next_rid += 1
        if deadline_at is not None:
            self._deadlines = True
        if E:
            self.metrics.resumed_requests += 1
            self.metrics.resume_tokens_reused += E
        self._queue.append(h)
        return h

    def _free_slot(self) -> Optional[int]:
        for s in range(self.max_slots):
            if self._slot_req[s] is None:
                return s
        return None

    def _bucket(self, T0: int) -> int:
        return min(bucket_pow2(T0, floor=self.min_bucket), self.max_len)

    def _retire(self, s: int, reason: str):  # band-verb: retire
        h = self._slot_req[s]
        h.done = True
        h.finish_reason = reason
        self._slot_req[s] = None
        self._alive[s] = False
        self._spec_ctx.pop(s, None)
        if self._adapter_pool is not None:
            # drop the request's adapter pin (the residency ref keeps
            # it warm); the band resets to the zero adapter so a freed
            # pool slot is never reachable through a stale index
            self._adapter_pool.release(int(self._aidx[s]))
            self._aidx[s] = 0
            self._mark_dirty("aidx")
        self._free_slot_blocks(s)
        self.metrics.kv_blocks_in_use = self._alloc.blocks_in_use
        self._mark_dirty("alive")

    def _emit(self, s: int, token: int) -> bool:
        """Append one generated token to slot s's request; retire on EOS
        or budget (EOS on the budget-exhausting step reports 'eos').
        Returns True if the slot was retired."""
        h = self._slot_req[s]
        if self._injector is not None \
                and getattr(self._injector, "garbled", False):
            # garble@ drill (ISSUE 15): wrong-but-FINITE output — every
            # emitted token is shifted to a different valid vocab id.
            # Sticky by design (a faulty core keeps computing wrong);
            # the numeric traps never fire, only a known-answer canary
            # mismatch can catch it. Applied at the emission bus, so
            # real requests AND canaries on this engine garble alike.
            token = (int(token) + 1) % int(self._cfg.vocab)
        h.tokens.append(int(token))
        st = self._spec_ctx.get(s)
        if st is not None:  # keep the drafting index current in O(1)
            ctx = st["ctx"]
            ctx.append(int(token))
            pair = (ctx[-2], ctx[-1])
            st["from"] = st["map"].get(pair)
            st["map"][pair] = len(ctx)
        self._counts[s] += 1
        self.metrics.tokens_out += 1
        if h.eos_id is not None and int(token) == int(h.eos_id):
            self._retire(s, "eos")
            return True
        if len(h.tokens) >= h.max_new_tokens:
            self._retire(s, "budget")
            return True
        return False

    def _admit(self, h: ServingHandle, s: int) -> bool:  # band-verb: alias
        """Try to assign a free slot: match the longest cached prefix
        chain, ALIAS its physical blocks into the slot's table
        (ref-counted, zero-copy), copy-on-write any aliased block the
        suffix must write into, and reserve the worst-case remainder
        from the pool. Returns False — leaving the request QUEUED and
        the engine state untouched — when the pool cannot cover the
        reservation even after reclaiming idle trie blocks. No model
        compute happens here — chunks run in step()'s prefill phase.
        A resumed request's context is prompt + already-emitted tokens
        (full_prompt): the pool match below is how "restart from
        scratch" becomes "alias the finished part, keep decoding"."""
        T0 = h.full_prompt.shape[0]
        Bt = self.kv_block_tokens
        need_total = self._blocks_for(T0 + h.max_new_tokens)
        aslot = 0
        pool = self._adapter_pool
        pc = self.prefix_cache
        if h.adapter is not None:
            # the trie is keyed by TOKENS alone, but an adapted model
            # writes adapter-specific K/V: aliasing another tenant's
            # blocks (or publishing ours) would serve tenant A's cache
            # rows to tenant B — adapter-carrying requests skip the
            # shared prefix pool entirely (_publish applies the same
            # rule on the way out)
            pc = None
        # a pure PROBE: a block-starved request retries every step, and
        # retries must not inflate hit/miss stats or restamp LRU order
        # — record_hit/record_miss fire once the admission resolves
        m = pc.match(h.full_prompt, record=False) if pc is not None else None
        if m is not None and m.length == 0:
            m.release()
            m = None
        cursor = n_alias = n_cow = 0
        need_new = need_total
        if m is not None:
            matched = m.length
            # the last prompt token must be COMPUTED — its logits seed
            # the first generated token — so the suffix cursor stops at
            # T0-1 even when the whole prompt is cached…
            cursor = min(matched, T0 - 1)
            n_alias = matched // Bt
            # …and any aliased block overlapping [cursor, T0) (only the
            # last one can: cursor >= (n_alias-1)*Bt) is copy-on-write
            # privatised below, never written through
            n_cow = n_alias - min(n_alias, cursor // Bt)
            need_new = need_total - (n_alias - n_cow)
            self._reclaim_for(need_new)
            if self._alloc.available < need_new:
                # the held match PINS the very chain reclaim would have
                # to evict (a fully-cached prompt whose worst case
                # fills the pool would deadlock here forever) — drop
                # the alias plan and fall through to a cold-miss
                # admission, where those blocks are reclaim's fair game
                m.release()
                m = None
                cursor = n_alias = n_cow = 0
                need_new = need_total
        if m is None:
            self._reclaim_for(need_new)
            if not self._alloc.reserve(need_new):
                return False  # saturated: stay queued (backpressure)
            if self._win is not None \
                    and not self._win.admit(s, T0 + h.max_new_tokens):
                # the window pool holds every slot's bound, so this is
                # structurally unreachable: kept as the loud unwind
                self._alloc.release_reservation(need_new)
                return False
            if pool is not None:
                # pin the request's adapter AFTER the block
                # reservation: a block-starved request retries every
                # scheduler step, and acquiring first would inflate
                # adapter hit counts and restamp the pool LRU per
                # retry (the prefix-probe discipline, applied to
                # adapters). A pool whose every slot is held by live
                # requests leaves this request QUEUED — unwind the
                # block reservation and retry next step
                aslot = pool.acquire(h.adapter)
                if aslot is None:
                    self._alloc.release_reservation(need_new)
                    return False
            if pc is not None:
                pc.record_miss()
        else:
            try:
                # the match is ref-held until the aliases take their
                # own pool refs: reclaim/eviction cannot free a block
                # mid-alias
                if not self._alloc.reserve(need_new):
                    return False  # unreachable single-threaded; defensive
                if pool is not None:
                    # h.adapter is None on this branch (adapter
                    # requests never match the trie): the zero-slot
                    # pin, which always succeeds
                    aslot = pool.acquire(None)
                if self._fp is not None and n_alias:
                    # ISSUE 15 fingerprint spot-check — the aliased
                    # re-open audit point: a DIFFERENT request is about
                    # to attend through these physical blocks (and a
                    # failover/migration RESUME re-attaches to the pool
                    # through this very match), so a silently flipped
                    # block must be caught HERE, before it serves a
                    # single prefix-cache hit. Placed AFTER the
                    # reservation so a block-starved request's per-step
                    # admission retries never pay the device reduction
                    # (the pure-probe discipline); on a mismatch the
                    # trip latches the engine, so the half-taken
                    # reservation dies with it. All dispatches are
                    # issued before the first host sync, so an N-block
                    # chain costs ~one round-trip, not N (a fixed-shape
                    # batched reduction would save the dispatches too —
                    # the PERF.md honest-overhead row tracks it)
                    if self._fp_fn is None:
                        self._fp_of(int(m.payloads[0]))  # trace once
                    pend = [(int(m.payloads[d]),
                             self._fp_fn(self._cache,
                                         jnp.int32(int(m.payloads[d]))))
                            for d in range(n_alias)]
                    for bid, fp_d in pend:
                        if not self._fp.check(bid, float(fp_d)):
                            self._trip(
                                "fingerprint",
                                "KV block %d fingerprint mismatch on "
                                "aliased re-open (committed %r)"
                                % (bid, self._fp.expected(bid)))
                pc.record_hit(m)  # the probe resolves to a real use
                keep = n_alias - n_cow
                for d in range(keep):
                    bid = int(m.payloads[d])
                    self._alloc.incref(bid)
                    self._tables[s, d] = bid
                for d in range(keep, n_alias):
                    nb = self._alloc.alloc_reserved()
                    if self._cow_fn is None:
                        self._cow_fn = self._make_cow()
                    self._cache = self._cow_fn(
                        self._cache, jnp.int32(nb),
                        jnp.int32(int(m.payloads[d])))
                    self._tables[s, d] = nb
                    self.metrics.cow_blocks += 1
            finally:
                m.release()
        # ISSUE 16 handoff import: the migration/failover package ships
        # the source replica's CLOSED prompt blocks as self-describing
        # store records — upload each into a freshly materialised block
        # (consuming this slot's reservation, exactly like a prefill
        # allocation would) after token/crc checks, then verify the
        # RECOMPUTED on-device fingerprint against the record's: the
        # PR 15 fingerprint IS the transfer checksum. Any failure stops
        # the import at the last good block (a child's KV attends
        # through its ancestors — importing past a hole would be
        # wrong); the prefill cursor then covers the shortfall, so the
        # fallback is re-prefill: counted, never wrong.
        n_imp = 0
        imp_fail = False
        package = h.handoff
        store = self._kv_store
        if package:
            for d in range(n_alias,
                           min(len(package), self.blocks_per_slot)):
                rec = package[d]
                blk = tuple(int(t)
                            for t in h.full_prompt[d * Bt:(d + 1) * Bt])
                if (rec.get("kv_quant", "none") != self.kv_quant
                        or tuple(rec["tokens"]) != blk
                        or len(rec["payload"]) != rec["nbytes"]
                        or payload_crc(rec["payload"]) != rec["crc"]):
                    imp_fail = True
                    break
                bid = self._alloc.alloc_reserved()
                ok = self._upload_block_record(rec, bid)
                fp_d = self._fp_of(bid) if ok else None
                if not ok or not self._record_fp_ok(rec, fp_d):
                    # the freed block does NOT restore the reservation
                    # alloc_reserved consumed — re-reserve it (the just-
                    # freed block guarantees success) so the slot's
                    # reserved-tail accounting stays balanced
                    self._decref_block(bid)
                    self._alloc.reserve(1)
                    if store is not None and ok:
                        store.quarantine(rec["key"])
                        self.metrics.store_quarantined += 1
                    imp_fail = True
                    break
                if self._fp is not None:
                    self._fp.commit(bid, fp_d)
                self._tables[s, d] = bid
                n_imp += 1
            if n_imp:
                cursor = min((n_alias + n_imp) * Bt, T0 - 1)
                self.metrics.handoff_blocks_imported += n_imp
                self.metrics.handoff_tokens_imported += n_imp * Bt
                h.handoff_imported = n_imp * Bt
        # the zero-recompute audit: closed-block prompt tokens the
        # source had finished vs where this admission's prefill cursor
        # actually starts. The final prompt token (T0-1) always
        # computes — its logits seed the first generated token — so
        # the contract excludes it. A resumed admission with NO package
        # charges every closed block it re-prefills (handoff absent or
        # disabled: the counted degradation path).
        expected = 0
        if package:
            expected = min(len(package) * Bt, T0 - 1)
        elif h.resume_len > 0:
            expected = min((T0 // Bt) * Bt, T0 - 1)
        recomputed = max(0, expected - cursor)
        self.metrics.tokens_recomputed_at_migration += recomputed
        if package:
            if recomputed > 0 or imp_fail:
                self.metrics.handoff_fallbacks += 1
                h.handoff_fallback = True
            else:
                # clean: imported, or already resident via the warmed
                # trie (n_imp == 0 with full alias coverage) — either
                # way zero tokens re-prefilled
                self.metrics.handoff_imports += 1
            # every judged package reports an outcome — the journal's
            # done record must account for the assign's handoff
            # side-band (J011), silence is never an answer
            h.handoff_outcome = {"imported": h.handoff_imported,
                                 "fallback": h.handoff_fallback}
            h.handoff = None  # release the payload bytes
        if self._has_state:
            with self.metrics.phase("engine.state_reset", rid=h.rid):
                self._reset_slot_state(s)
        self._n_alloc[s] = n_alias + n_imp
        self._reserved_tail[s] = need_new - n_cow - n_imp
        if pc is not None:
            self.metrics.prefix_hit_tokens.append(cursor if n_alias else 0)
        h.queue_wait_s = time.monotonic() - h.submit_t
        self.metrics.queue_wait_s.append(h.queue_wait_s)
        self.metrics.kv_blocks_in_use = self._alloc.blocks_in_use
        self._slot_req[s] = h
        self._limits[s] = T0 + h.max_new_tokens
        self._aidx[s] = aslot
        self._mark_dirty("tables", "limits", "aidx")
        # the first-token sampling key is per-request, not per-chunk:
        # computed once here, consumed on the prompt's final chunk. A
        # resumed request's first NEW token is overall token index
        # resume_len — the fold_in schedule continues where the dead
        # incarnation stopped, so sampled outputs stay resume-invariant
        self._prefill_state[s] = {
            "handle": h, "cursor": cursor,
            "key": jax.random.fold_in(
                jax.random.PRNGKey(h.seed), h.resume_len),
        }
        self._prefill_q.append(s)
        return True

    def _publish(self, s: int, h: ServingHandle):  # band-verb: serialize
        """Publish the finished prompt's prefix blocks (up to the
        request's publish boundary) back to the pool — zero-copy: the
        trie takes a ref on the slot's PHYSICAL block ids. Novel blocks
        only; a chain the trie already holds gains nothing."""
        pc = self.prefix_cache
        if pc is None or h.adapter is not None:
            # adapter-specific K/V must never enter the shared trie
            # (the _admit cross-tenant poisoning rule, outbound half)
            return
        T0 = h.full_prompt.shape[0]
        bound = T0 if h.publish_len is None else min(h.publish_len, T0)
        Bt = pc.block_tokens
        n_blocks = bound // Bt
        if n_blocks < 1:
            return
        store = self._kv_store

        def _take(d):
            bid = int(self._tables[s, d])
            self._alloc.incref(bid)
            fp = None
            if self._fp is not None or store is not None:
                fp = self._fp_of(bid)
            if self._fp is not None:
                # ISSUE 15: publish is where a block CLOSES — it is
                # full (only whole prompt blocks publish; the slot's
                # later decode writes land past them) and any future
                # write goes through COW to a private copy. Commit the
                # fingerprint now; aliased re-opens verify against it.
                self._fp.commit(bid, fp)
            if store is not None:
                # ISSUE 16 write-through: a closing block leaves the
                # replica as a self-describing record, the committed
                # fingerprint riding along as the transfer checksum.
                # Novel blocks only (publish skips trie-held chains):
                # a chain the store evicted since its first spill is
                # NOT re-spilled — accepted staleness, the fallback
                # path covers it.
                payload, meta = self._serialize_block(bid)
                store.put(make_block_record(
                    keys[d], keys[d - 1] if d else 0,
                    tuple(int(t)
                          for t in h.full_prompt[d * Bt:(d + 1) * Bt]),
                    fp, payload, meta, kv_quant=self.kv_quant))
                self.metrics.store_spilled_blocks += 1
            return bid

        with self.metrics.phase("engine.publish"):
            # chain keys for the store records: one fold per publish
            # call, shared with the trie summary and the router
            # (fold_key) — the store is keyed by the SAME chain
            # identity the trie uses
            keys = (chain_keys(h.full_prompt[:n_blocks * Bt], Bt)
                    if store is not None else None)
            pc.publish(h.full_prompt, n_blocks, _take)

    def _run_chunk(self, s: int) -> bool:  # band-verb: resume
        """Advance slot s's prefill by one chunk; on the final chunk,
        publish the prefix, activate the slot, and emit the first
        token. Returns True when the prefill completed."""
        st = self._prefill_state[s]
        h = st["handle"]
        T0 = h.full_prompt.shape[0]
        cursor = st["cursor"]
        c = T0 - cursor
        if self.prefill_chunk_tokens is not None:
            c = min(c, self.prefill_chunk_tokens)
        Cb = self._bucket(c)
        m = self.metrics
        with m.phase("engine.prefill_chunk", row="prefill_T%d" % Cb,
                     rid=h.rid, bucket=Cb, tokens=c):
            table_row = self._tables[s]
            with m.phase("engine.alloc_blocks"):
                self._ensure_blocks(s, cursor, cursor + c)
                if self._win is not None:
                    # the chunk READS the window behind it through the
                    # table as it stands, and WRITES through the table
                    # as the release leaves it: rows [full, window
                    # read, window write, the slot's index]
                    wread = self._win.tables[s].copy()
                    self._advance_window([(s, cursor, cursor + c)])
                    table_row = np.stack([
                        table_row, wread, self._win.tables[s],
                        np.full_like(table_row, s)])
                elif self._has_state:
                    # rows [the table's, the slot's index]: the chunk
                    # carries the slot's own state
                    table_row = np.stack([table_row,
                                          np.full_like(table_row, s)])
            padded = np.zeros(Cb, np.int32)
            padded[:c] = h.full_prompt[cursor:cursor + c]
            fn = self._chunk_fn(Cb)
            with m.phase("engine.upload"):
                args = (jnp.asarray(padded), jnp.int32(cursor),
                        jnp.asarray(table_row), jnp.int32(c),
                        jnp.float32(h.temperature))
                adapter = self._adapter_args(jnp.int32(int(self._aidx[s])))
            with m.phase("engine.dispatch"):
                self._cache, first, trap_d, scale_d = fn(
                    self._params, self._cache, *args, st["key"], **adapter)
            st["cursor"] = cursor + c
            m.prefill_chunks += 1
            m.prefill_tokens_computed += c
            m.kv_blocks_in_use = self._alloc.blocks_in_use
            if st["cursor"] < T0:
                # mid-prompt chunk: dispatch only, nothing to read back
                # — the batched decode below overlaps with it
                return False
            with m.phase("engine.device_wait") as wait:
                first = int(np.asarray(first))  # blocks: the token is real
            if self.integrity_traps:
                # the trap rides the same readback sync (mid-prompt
                # chunks stay dispatch-only: a mid-chunk NaN propagates
                # through the cache into THIS final chunk's logits)
                with m.phase("engine.integrity"):
                    self._check_integrity(trap_d, np.asarray(scale_d),
                                          "prefill chunk", slots=[s])
            h.ttft_s = wait.t1 - h.submit_t
            m.ttft_s.append(h.ttft_s)
            m.prefills += 1
            self._publish(s, h)
            with m.phase("engine.emit"):
                del self._prefill_state[s]

                self._tok[s] = first
                self._pos[s] = T0
                self._alive[s] = True
                self._temps[s] = h.temperature
                # a resumed request continues the ORIGINAL fold_in
                # schedule: its next sampled token is overall index
                # resume_len
                self._counts[s] = h.resume_len
                self._base_keys[s] = np.asarray(jax.random.PRNGKey(h.seed))
                # device-side EOS judgment for the decode step (-1 =
                # none); the _mark_dirty() below re-uploads it with
                # everything else
                self._eos[s] = -1 if h.eos_id is None else int(h.eos_id)
                if self.spec_draft_len is not None:
                    # seed the drafting index from the context once
                    # (O(T0)); _emit keeps it current per token from
                    # here on
                    ctx = [int(t) for t in h.full_prompt]
                    bmap = {}
                    for i in range(len(ctx) - 1):
                        bmap[(ctx[i], ctx[i + 1])] = i + 2
                    self._spec_ctx[s] = {"ctx": ctx, "map": bmap,
                                         "from": None}
                self._mark_dirty()  # all bands: slot s changed everywhere
                # may retire immediately (max_new==1 / eos)
                self._emit(s, first)
        return True

    def _drop_slot(self, s: int, reason: str):
        """Terminate slot s's request without emitting: clear any
        pending prefill cursor, then retire (frees blocks + the
        reserved tail). The deadline/cancel path — the slot's work is
        abandoned, not completed."""
        if s in self._prefill_state:
            del self._prefill_state[s]
            self._prefill_q.remove(s)
        self._retire(s, reason)

    def _expire_sweep(self) -> bool:
        """Enforce per-request deadlines at every queue hop (ISSUE 8):
        queued requests expire before admission, prefilling slots
        before their next chunk, decoding slots before the next batched
        step — the scheduler stops spending compute on a request the
        moment it cannot be answered in budget. Expiry is a VERDICT
        (finish_reason 'expired', done=True), never a silent hang."""
        if not self._deadlines:
            return False
        now = time.monotonic()
        changed = False
        seen = False  # any deadline still pending after this sweep?
        keep: collections.deque = collections.deque()
        while self._queue:
            h = self._queue.popleft()
            if h.deadline_at is not None and now >= h.deadline_at:
                h.done = True
                h.finish_reason = "expired"
                self.metrics.expired += 1
                changed = True
            else:
                seen = seen or h.deadline_at is not None
                keep.append(h)
        self._queue = keep
        for s in range(self.max_slots):
            h = self._slot_req[s]
            if h is not None and h.deadline_at is not None:
                if now >= h.deadline_at:
                    self._drop_slot(s, "expired")
                    self.metrics.expired += 1
                    changed = True
                else:
                    seen = True
        if not seen:
            # nothing left carries a deadline: drop the latch (the
            # next deadline submit re-arms it) so a long-lived engine
            # does not pay the sweep forever for one SLO request
            self._deadlines = False
        return changed

    def cancel(self, rid) -> bool:
        """Terminate one request (by this ENGINE's rid) wherever it is
        — queued, prefilling, or decoding — freeing its slot and
        blocks; the handle finishes with reason 'cancelled' and its
        partial tokens. The fleet uses this to claw work back from a
        demoted (gray-slow) replica after hedging it to a survivor;
        the demoted engine must stop spending steps on it. Returns
        False if the rid is unknown or already finished."""
        for h in self._queue:
            if h.rid == rid and not h.done:
                self._queue.remove(h)
                h.done = True
                h.finish_reason = "cancelled"
                self.metrics.cancelled += 1
                return True
        for s in range(self.max_slots):
            h = self._slot_req[s]
            if h is not None and h.rid == rid:
                self._drop_slot(s, "cancelled")
                self.metrics.cancelled += 1
                return True
        return False

    def abort(self, exc: BaseException):
        """Latch the engine as failed and propagate `exc` into every
        pending handle (queued, prefilling, or decoding): their
        `result()` raises instead of blocking forever. Called
        internally when a step dies, and externally by whatever thread
        drives the engine (a fleet replica loop) when IT dies between
        steps. Idempotent; the first failure wins."""
        if self._failed is None:
            if isinstance(exc, EngineFailed):
                self._failed = exc
            else:
                self._failed = EngineFailed(
                    "engine%s failed: %r" % (
                        "" if self.replica_id is None
                        else " (replica %s)" % self.replica_id,
                        exc),
                    replica=self.replica_id)
                self._failed.__cause__ = exc
        for h in list(self._queue) + list(self._slot_req):
            if h is not None and not h.done and h.error is None:
                h.error = self._failed

    def step(self) -> bool:
        """One scheduler iteration: expire anything past its deadline
        (queued, prefilling, or decoding — a verdict before another
        token of work is spent on it), admit queued requests into free
        slots (prefix aliasing + block reservation; a block-starved
        pool leaves them queued), advance pending prefills by up to
        `max_prefills_per_step` chunks (FCFS), then ONE batched decode
        — or, with `spec_draft_len` set, ONE batched speculative
        verify emitting up to K tokens per slot — advancing every live
        slot; retirements free blocks and slots for the next step's
        admissions. Returns False when there was nothing to do (queue
        empty, no pending prefill, no live slots).

        Each call ticks the fault injector (PADDLE_FAULT, or the
        engine's own `fault_injector`) BEFORE doing work, so
        `kill@N`/`exc@N`/`delay@N:dur` specs land mid-decode — the
        fleet kill drills' step boundary. Any failure (injected or
        real) aborts every pending handle and latches the engine: the
        compiled steps donate their cache buffers, so a step that died
        mid-dispatch must never run again on the half-donated cache."""
        if self._sched_hook is not None:
            self._sched_hook.yield_point(
                "engine:%s:step" % (self.replica_id or ""))
        if self._failed is not None:
            raise self._failed
        inj = self._injector
        if inj is None:
            inj = self._injector = (
                _fi.default_injector()
                if os.environ.get(_fi.ENV_VAR) else _fi.FaultInjector("")
            )
        m = self.metrics
        m.steps += 1
        m.step_phases.clear()
        try:
            with m.phase("engine.step", step=m.steps) as whole:
                if inj.active:
                    inj.tick()
                    if inj.take_flip():
                        # flip@ drill (ISSUE 15): silent KV corruption
                        # — finite garbage into one resident block,
                        # invisible to the numeric traps, caught only
                        # by the fingerprint spot-check at aliased
                        # re-open
                        self._flip_resident_block()
                out = self._step_inner()
        except Exception as exc:
            self.abort(exc)
            raise
        # step-latency EWMA INCLUDES the injector tick: an injected
        # gray stall (slow@) is exactly what the fleet's health score
        # must see here
        m.observe_step(whole.t1 - whole.t0)
        return out

    def _step_inner(self) -> bool:
        progressed = False
        if self._deadlines:
            with self.metrics.phase("engine.expire"):
                progressed = self._expire_sweep()
        while self._queue:
            s = self._free_slot()
            if s is None:
                break
            h = self._queue[0]
            with self.metrics.phase("engine.admit", rid=h.rid):
                admitted = self._admit(h, s)
            if not admitted:
                break  # block-starved: FCFS head waits, so do followers
            self._queue.popleft()
            progressed = True

        cap = self.max_prefills_per_step
        chunks = 0
        for s in list(self._prefill_q):
            if cap is not None and chunks >= cap:
                break
            if self._run_chunk(s):
                self._prefill_q.remove(s)
            chunks += 1
            progressed = True

        if self.spec_draft_len is not None:
            if not self._alive.any():
                return progressed
            with self.metrics.phase("engine.decode", row="spec_verify"):
                self._spec_step()
        elif not self._decode_phase():
            # reached even with no host-live slot: a step in flight
            # may still hold the tokens that retire the last requests
            return progressed

        frag = 0
        for s in np.nonzero(self._alive)[0]:
            frag += int(self._n_alloc[s]) * self.kv_block_tokens \
                - int(self._pos[s])
        for s in self._prefill_q:
            frag += int(self._n_alloc[s]) * self.kv_block_tokens \
                - int(self._prefill_state[s]["cursor"])
        self.metrics.kv_frag_tokens = frag
        self.metrics.kv_blocks_in_use = self._alloc.blocks_in_use
        return True

    def _count_decode_step(self):
        """One decode step (plain, or a verify step) was dispatched:
        slot occupancy and, for a family with window tables or state,
        the bytes its caches hold by kind."""
        m, alive = self.metrics, self._alive
        m.decode_steps += 1
        m.occupancy.append(float(alive.sum()) / self.max_slots)
        win = self._win
        if self._by_kind:
            # by kind of cache, the kinds the family has
            n_live = int(alive.sum())
            used = {"full": self._alloc.blocks_in_use * self.kv_block_bytes}
            if win is not None:
                used["window"] = (win.alloc.blocks_in_use
                                  * win.alloc.block_bytes)
            if self._has_state:
                used["state"] = n_live * self._state_bytes_per_slot
            m.cache_bytes_in_use = used
            m.cache_bytes_per_slot.append(
                sum(used.values()) / max(n_live, 1))

    # ------------------------------------------------------------------
    # the decode loop: one program, read in the same step() or one later
    # ------------------------------------------------------------------
    def _can_chain(self) -> bool:
        """Step N+1 may chain off step N's un-read device outputs only
        while the device-advanced bands still carry device truth: any
        host event since dispatch (admission, cancel, expiry, a
        host-side divergence) dirtied one of them and the chain must
        break — read first, re-upload host truth, then dispatch."""
        return not (self._dirty & _DEVICE_ADVANCED)

    def _decode_phase(self) -> bool:
        """The plain (non-speculative) decode phase of a step(): read
        the step in flight (if any) and keep the pipeline one step
        deep (`async_dispatch`), or dispatch and read one step in-line
        (lock-step). Returns False only when there is genuinely
        nothing to do — no live slot AND no step in flight (one may
        still hold the tokens that retire the final requests, so it
        must be read even with zero host-live slots)."""
        rec, self._inflight = self._inflight, None
        if rec is None and not self._alive.any():
            return False
        m = self.metrics
        with m.phase("engine.decode", row="decode_step"):
            if rec is None:
                rec = self._dispatch_decode()
                if self.async_dispatch:
                    # one-step-behind emission: read next step
                    self._inflight = rec
                else:
                    self._read_decode(rec)
                return True
            ahead = None
            if self._alive.any():
                if self._can_chain():
                    # enqueue step N+1 off step N's device outputs
                    # BEFORE reading N: the emit/schedule work below
                    # runs under N+1's device compute (the whole point)
                    ahead = self._dispatch_decode(prev=rec)
                    m.decode_dispatched_ahead += 1
                else:
                    m.decode_chain_breaks += 1
            self._read_decode(rec)
            if not self._alive.any():
                # N retired the last live slot: a step already
                # dispatched off it holds no lane the host would emit
                # (parked on the device too, where the mirrors agree)
                # — nothing to read
                ahead = None
            elif ahead is None:
                # chain broken by a host event: host truth is current
                # again now that N is read — refill the pipeline
                ahead = self._dispatch_decode()
            self._inflight = ahead
        return True

    def _dispatch_decode(self, prev=None):
        """Enqueue one compiled decode step (a token a slot). `prev`
        chains this dispatch off the given un-read step's output bands
        (host mirrors are one step stale then — the block horizon
        covers 2 positions so the device never writes past the
        table). Its packed result starts for the host at once.

        A family with window tables (ISSUE 30) has them advanced here
        for the position THIS step writes, `pos + horizon - 1`: the
        mirror's on a fresh dispatch, one past it on a chained one
        (every host-live slot advances by exactly one in the step in
        flight, or retires in it). Never further ahead than the step
        being dispatched, which is why running a step ahead is safe:

        * a block released here may still be named by the table of
          the step in flight. Each dispatch carries its own uploaded
          snapshot of `tables`, the device runs programs in order, and
          whatever takes the block next (another slot's write in this
          step, a later chunk) is queued behind the step in flight;
        * a slot the step in flight retires on the device is still
          host-live here. Its advance is wasted but harmless: it stays
          inside the slot's reservation (`q < limits - 1`, as for the
          full pool's blocks), the device parks the lane, and
          `_free_slot_blocks` returns every block and the unreached
          reservation when that step is read — `held(s) <= per_slot`
          at every dispatch;
        * a chain break reads first, and the fresh dispatch advances
          at the now-current `pos`: every position is visited once, in
          order (the edge test below relies on it), and a position
          visited twice finds `advance` idempotent."""
        live = np.nonzero(self._alive)[0]
        horizon = 2 if prev is not None else 1
        m = self.metrics
        with m.phase("engine.alloc_blocks"):
            for s in live:
                p = int(self._pos[s])
                # positions < limits-1 are the only ones ever written
                # (the budget rule parks a slot after its write at
                # limits-2)
                self._ensure_blocks(
                    s, p, min(p + horizon, int(self._limits[s]) - 1))
            if self._win is not None:
                # a window table changes only where a write opens a
                # block or the window's tail leaves one; `q` is the
                # position this step writes (a slot on its last write
                # in the step in flight has none)
                Bt, q = self.kv_block_tokens, self._pos[live] + (horizon - 1)
                edge = ((q % Bt == 0) | ((q + 1 - self._win.window) % Bt
                                         == 0)) & (q < self._limits[live] - 1)
                if edge.any():
                    self._advance_window(
                        [(s, w, w + 1) for s, w in zip(live[edge],
                                                       q[edge])])
        rest = ("tables", "temps", "base_keys", "limits", "eos", "aidx")
        if prev is None:
            tok_d, pos_d, alive_d, counts_d, *rest_d = self._bands(
                "tok", "pos", "alive", "counts", *rest)
        else:
            tok_d, pos_d, alive_d, counts_d = prev["bands"]
            rest_d = self._bands(*rest)
        tables_d, temps_d, keys_d, limits_d, eos_d, aidx_d = rest_d
        adapter = self._adapter_args(aidx_d)
        with m.phase("engine.dispatch"):
            self._cache, *bands, packed = self._decode_fn(
                self._params, self._cache, tables_d, tok_d, pos_d,
                alive_d, temps_d, counts_d, keys_d, limits_d, eos_d,
                **adapter)
        packed.copy_to_host_async()
        self._count_decode_step()
        return {"bands": bands, "packed": packed,
                "slots": [(int(s), self._slot_req[int(s)])
                          for s in live]}

    def _read_decode(self, rec):  # band-verb: sync
        """Read one dispatched step — ONE blocking read of its packed
        result — and emit its tokens. Lane discipline: -1 lanes are
        parking padding (the slot was dead on the device at dispatch)
        and are discarded; a slot whose handle changed since dispatch
        (expired, cancelled, re-tenanted) has its lane discarded too —
        an expired request keeps the tokens already read and nothing
        more. The trap flags and the magnitude are judged, from the
        host values just read, BEFORE any token emits: a tripped step
        becomes an integrity event INSTEAD of tokens; an all-parked
        step is skipped so the spike EWMA never ingests masked
        zeros."""
        m = self.metrics
        with m.phase("engine.device_wait"):
            toks, traps, scale, bands, stats = self._unpack(rec["packed"])
        for name, v in zip(self._step_counters, stats):
            getattr(m, name).append(float(v))
        if self.integrity_traps:
            with m.phase("engine.integrity"):
                verdict = ("ok" if not (toks >= 0).any() else
                           self._sentinel.observe(bool(traps.any()), scale))
                if verdict != "ok":
                    self._trip_verdict(verdict, traps, scale, "decode")
        with m.phase("engine.emit"):
            for s, h in rec["slots"]:
                if self._slot_req[s] is not h or not self._alive[s]:
                    continue  # expired/cancelled/re-tenanted: discard
                t = int(toks[s])
                if t < 0:
                    continue  # parked lane
                self._pos[s] += 1  # the token just read sat at pos
                self._tok[s] = t
                self._emit(s, t)
        # adopt the step's outputs as device truth (steady loop
        # re-uploads nothing) — but only when the host mirrors, advanced
        # by the emit loop above, agree with the bands the packed result
        # carries: a host-side divergence (fault drills shifting emitted
        # tokens' EOS judgment, a mid-flight expiry) re-uploads host
        # truth instead of silently trusting the device schedule
        if all(np.array_equal(getattr(self, "_" + name), band)
               for name, band in zip(_ADVANCED_ORDER, bands)):
            self._dev.update(zip(_ADVANCED_ORDER, rec["bands"]))
            self._dirty.difference_update(_DEVICE_ADVANCED)
        else:
            self._mark_dirty(*_DEVICE_ADVANCED)

    def _draft_window(self, s: int) -> np.ndarray:
        """Self-drafting by prompt lookup: continue the context's last
        bigram from its most recent earlier occurrence (Leviathan et
        al.'s speculative schedule with the request's own text as the
        draft model — free drafts, no second network). Unfilled draft
        rows are -1: never accepted (candidates are valid vocab ids),
        so a draft-less window degrades to plain one-token decode."""
        K = self.spec_draft_len
        w = np.full(K, -1, np.int32)
        w[0] = self._tok[s]  # the pending (unwritten) token leads
        st = self._spec_ctx.get(s)
        if st is not None and st["from"] is not None:
            # tokens following the tail bigram's previous occurrence
            cont = st["ctx"][st["from"]:st["from"] + K - 1]
            w[1:1 + len(cont)] = cont
        return w

    def _spec_step(self):
        """One speculative decode phase: build every live slot's
        K-token window (pending token + K-1 drafts), verify in ONE
        compiled batched step, then emit the model's own candidates up
        to the first draft mismatch (plus the bonus token) — greedy
        emission is exactly the plain path's, only batched in time.
        Host-side acceptance re-uploads the tok/pos/counts bands next
        step (the documented spec trade: ~3 small h2d per multi-token
        step instead of zero per single-token step)."""
        K = self.spec_draft_len
        metrics, phase = self.metrics, self.metrics.phase
        live = np.nonzero(self._alive)[0]
        window = np.zeros((self.max_slots, K), np.int32)
        with phase("engine.alloc_blocks"):
            for s in live:
                lo = int(self._pos[s])
                self._ensure_blocks(
                    s, lo, min(lo + K, int(self._limits[s])))
        for s in live:
            window[s] = self._draft_window(s)
        tables_d, pos_d, alive_d, limits_d, temps_d, counts_d, keys_d, \
            aidx_d = self._bands("tables", "pos", "alive", "limits",
                                 "temps", "counts", "base_keys", "aidx")
        with phase("engine.upload"):
            window_d = jnp.asarray(window)
        adapter = self._adapter_args(aidx_d)
        with phase("engine.dispatch"):
            self._cache, cand_d, trap_d, scale_d = self._verify_fn(
                self._params, self._cache, tables_d, window_d, pos_d,
                alive_d, limits_d, temps_d, counts_d, keys_d, **adapter)
        with phase("engine.device_wait"):
            cand = np.asarray(cand_d)  # blocks; candidates are real
        if self.integrity_traps:
            with phase("engine.integrity"):
                self._check_integrity(trap_d, np.asarray(scale_d),
                                      "spec verify")
        self._count_decode_step()
        with phase("engine.emit"):
            for s in live:
                h = self._slot_req[s]
                # accepted drafts: longest window prefix the model
                # agrees with
                m = 0
                while m < K - 1 and window[s, m + 1] == cand[s, m]:
                    m += 1
                budget_left = h.max_new_tokens - len(h.tokens)
                n = min(m + 1, budget_left)
                self.metrics.spec_windows += 1
                # count only drafts actually PROPOSED (-1 rows are empty
                # lanes, not rejections) AND within the request's remaining
                # budget (a final window's over-budget lanes can never be
                # accepted): accept_rate stays an honest measure of draft
                # quality
                lanes = window[s, 1:max(1, budget_left)]
                self.metrics.spec_drafted += int((lanes >= 0).sum())
                adv = 0
                for j in range(n):
                    adv += 1
                    self._tok[s] = cand[s, j]
                    if self._emit(s, cand[s, j]):
                        break  # EOS/budget: later accepted drafts discarded
                self._pos[s] += adv  # one cache write per emitted token
                self.metrics.spec_accepted += max(0, adv - 1)
        # acceptance is a host decision: these bands re-upload next step
        self._mark_dirty("tok", "pos", "counts")

    def run(self) -> Dict[int, np.ndarray]:
        """Drive the engine until the queue drains and every slot
        retires; returns {request_id: full sequence} for every request
        completed during this call."""
        finished: Dict[int, np.ndarray] = {}
        # a retired handle never lingers in _slot_req, so everything
        # in-flight or queued right now is exactly this call's work
        pending = list(self._queue) + [
            h for h in self._slot_req if h is not None
        ]
        while self.step():
            pass
        for h in pending:
            if h.done:
                # full_prompt: a resumed request's sequence includes
                # the tokens the earlier incarnation already emitted
                finished[h.rid] = np.concatenate(
                    [h.full_prompt, np.asarray(h.tokens, np.int32)]
                )
        return finished

    # ------------------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def live_slots(self) -> int:
        return int(self._alive.sum())

    @property
    def prefilling_slots(self) -> int:
        return len(self._prefill_q)

    @property
    def kv_blocks_in_use(self) -> int:
        return self._alloc.blocks_in_use

    @property
    def kv_blocks_free(self) -> int:
        return self._alloc.free_blocks
