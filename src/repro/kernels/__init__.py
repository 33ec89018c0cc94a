"""Pallas TPU kernels for the compute hot-spots, each with a jnp oracle.

  ssm_scan        — chunked selective scan (the paper's j-step Φ pipelining)
  flash_attention — blocked online-softmax attention (causal/local/GQA/softcap)
  int8_matmul     — fixed-point MACC matmul (DSP48E1 → MXU int8 path)
  tanh_lut        — ROM-LUT activation via one-hot MXU gather (§IV-B)

All kernels ship ops.py (jit wrapper) and ref.py (oracle).  ``platform``
picks interpret mode from the backend (interpreter off-TPU, Mosaic on a
TPU); tests sweep shapes/dtypes in interpret mode against the oracle.
"""
