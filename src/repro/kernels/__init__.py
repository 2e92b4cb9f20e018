"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as <name>/kernel.py (pl.pallas_call + explicit BlockSpec
VMEM tiling), <name>/ops.py (jit'd public wrapper) and <name>/ref.py
(pure-jnp oracle).  All kernels are validated against their oracles in
interpret mode (tests/test_kernels.py) — TPU is the compile target, CPU
interpret mode is the correctness harness.

  port_stats      — batched per-port rho/tau reduction (scheduler hot spot)
  event_resolve   — per-event idle / first-waiting-per-port reduction of
                    the batched circuit calendar (pipeline/batch_circuit)
  alloc_scan      — the whole allocation scan as one on-chip loop
                    (pipeline/batch_alloc; its oracle is the jnp loop
                    there, so no ops.py / ref.py)
  lp_terms        — fused X^T P matmuls + row-max (ordering-LP oracle)
  flash_attention — GQA flash attention w/ causal + sliding window
  quant           — int8 quantize/dequantize for gradient compression
  mlstm_chunk     — fused chunkwise mLSTM with VMEM-resident matrix state
                    (the xlstm hillclimb's identified TPU endgame)
"""
