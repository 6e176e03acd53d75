"""The one vocabulary of device scopes (ISSUE 35).

Every family's compiled steps and the engine's step wrappers name
their parts from this tuple and from nothing else, so a device trace
can be read by layer part whatever the family: a scope rides each
operation's metadata (`op_name`) into the compiled program and from
there into the profiler's event for it. `jax.named_scope` writes
metadata and nothing else: the program the chip runs is the same
instruction for instruction (`tests/test_tpu_aot_compile.py` holds
the digests).

  lm_embed      token (and position) lookup, the embedding multiplier
  lm_attention  every kind of attention layer: pre-norm, q/k/v(/gate)
                products, rotary, the K/V write, the paged/window/
                cross kernel call, the output product, the residual
                add
  lm_state      Mamba-1, Mamba-2 and the gated memory unit: pre-norm,
                in-projection, conv, the state-update call, gate,
                out-projection, the residual add
  lm_mlp        pre-norm, gate-up, activation, down, the residual add
  lm_experts    router, sorts and row plans, both grouped products,
                the shared expert, the combine, the residual add
  lm_head       final norm, the logits product and its multipliers
  step_sample   the engine's argmax and categorical sampling
  step_traps    the engine's non-finite trap and magnitude reduce
  step_retire   device-side retirement and the step's packed result

A pre-norm belongs to the part it feeds, a residual add to the part
that produced the branch. A fusion carries ONE path, its root's: a
norm XLA fuses into the neighbouring product is filed with the
product. No scope goes inside a Pallas body.
"""

from __future__ import annotations

import jax

__all__ = ["SCOPES", "scope"]

SCOPES = ("lm_embed", "lm_attention", "lm_state", "lm_mlp", "lm_experts",
          "lm_head", "step_sample", "step_traps", "step_retire")


def scope(name: str):
    """`jax.named_scope(name)` for a name of the vocabulary; any other
    name is refused, so none can appear beside it."""
    if name not in SCOPES:
        raise ValueError("%r is not a device scope: %r" % (name, SCOPES))
    return jax.named_scope(name)
