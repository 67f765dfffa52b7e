"""Pallas TPU kernels for the performance hot-spots, with jnp oracles.

- vtrace_pallas         : batch-tiled backward time-scan (Eqs. 14-15)
- flash_attention_pallas: online-softmax causal/SWA attention, GQA-aware
- wkv6_pallas           : chunked RWKV-6 linear-attention recurrence
- paged_attention_pallas: block-table paged attention (varlen/decode/verify)
- paged_kv_write_pallas : aliased DMA row scatter into the paged KV pool
- fused_logprob_pallas  : vocab-streamed log-prob + entropy (RLVR hot-spot)
- ops                   : dispatch by platform (pallas on TPU, reference
                          elsewhere; pallas_interpret on request)
- ref                   : pure-jnp oracles, the CPU and autodiff path
"""
from repro.kernels import ops, ref
