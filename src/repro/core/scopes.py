"""Names of the `jax.named_scope`s on the quartet2 path.

A named scope adds one component to the JAX name stack, which lands in
every HLO instruction's `op_name` metadata and, on the device, in the
profiler's `tf_op` path of each XLA op. It changes metadata only, never
the computation (docs/CONVENTIONS.md §6). The benchmark reads the device
time of ops under QUANT_FWD, QUANT_BWD and RHT as the quantizers' share
of a training step (PERF.md).
"""

QUANT_FWD = "quant.fwd"    # forward NVFP4 quantizer (core/linear.py _fwd_quant)
QUANT_BWD = "quant.bwd"    # backward re-quantization: MS-EDEN or SR (_bwd_gemm)
RHT = "rht"                # randomized Hadamard transform (core/rht.py)
GEMM = "gemm"              # the (simulated NVFP4 or bf16) GEMM (_qmm / _mm)
OPTIMIZER = "optimizer"    # gradient clipping + optimizer update (train_step)
