"""Pallas TPU kernels for the paper's compute hot spots:

- era_kernel:     fused Enhanced-ERA aggregation sharpening (VPU-bound)
- quant_kernel:   fused min-max quantize-dequantize round trip (the
                  lossy wire-format simulation used by repro.compress)
- distill_kernel: soft-target CE over large (LM-vocab) class dims
                  (flash-softmax block accumulation)
- attn_kernel:    causal GQA flash attention for client forward passes
- round_kernel:   fused uplink codec + client reduction + sharpening of
                  a round (the scan engines' fused_round path)
- mlp_distill_kernel: every client's whole SGD distillation run in VMEM
                  (the engines' client distillation on TPU)

ops.py = jit'd wrappers (interpret mode on CPU); ref.py = jnp oracles.
"""
