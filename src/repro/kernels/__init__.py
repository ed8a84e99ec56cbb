"""Pallas TPU kernels for the repo's compute hot-spots.

  lans_kernel / lamb_kernel  fused 3-phase optimizer step (paper's apex
                             fused_lans analogue) + mixed-precision
                             cast-and-apply phase-2 variant
  paged_attention_kernel     fused paged-attention decode (streams KV
                             blocks via block-table scalar prefetch)
  flash_attention_kernel     blocked online-softmax training attention
                             with its own backward (scores never in HBM)
  ops                        jit'd public wrappers (tiling/layout contract)
  ref                        pure-jnp oracles the kernels are tested against

Authoring conventions (interpret-mode default, block-spec patterns, how
ref.py gates correctness, benchmark wiring) are documented in
docs/kernels.md.
"""
import jax

# Shared attention-mask value: large but FINITE negative, so masked-lane
# arithmetic underflows to exactly 0 (exp(NEG_INF - m) == 0) instead of
# producing inf - inf = NaN. Single-sourced here because the Pallas
# paged-attention kernel, its jnp oracle (ref.py) and the XLA paths in
# models/attention.py must underflow identically for the bit-exact
# paged-pallas == paged-xla greedy-token contract to hold.
NEG_INF = -2.3819763e38


def default_interpret() -> bool:
    """Pallas interpret mode unless running on a TPU.

    Every kernel wrapper takes ``interpret=None`` and resolves it here, so
    a TPU always runs the compiled kernels and the CPU backend always
    interprets them; passing True or False overrides the backend.
    """
    return jax.default_backend() != "tpu"
