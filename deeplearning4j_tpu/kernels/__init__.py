"""Hand-written Pallas TPU kernels for the ops where XLA's automatic
fusion leaves throughput on the table — the role the reference filled
with hand-optimized CUDA helpers (``libnd4j/.../helpers/cuda``), except
each kernel here is a few dozen lines of Python lowered through Mosaic.
"""
from deeplearning4j_tpu.kernels.expert_ffn import (expert_ffn,
                                                   expert_ffn_reference,
                                                   expert_route,
                                                   expert_row_plan)
from deeplearning4j_tpu.kernels.flash_attention import (
    attention, flash_attention, mask_to_bias, reset_route_log, route_log,
    trace_mesh, xla_attention)
from deeplearning4j_tpu.kernels.paged_attention import (
    pad_head_dim, paged_decode_attention, paged_decode_attention_reference,
    paged_decode_write_attention, paged_gather, paged_head_rows,
    paged_heads_a_row, paged_pool_rows, paged_pool_shape, paged_pool_width,
    paged_route, paged_verify_attention, paged_verify_attention_reference,
    paged_walk_blocks, paged_walk_extent, softmax_with_sink)
from deeplearning4j_tpu.kernels.ssm_step import (ssm_route, ssm_step,
                                                 ssm_step_reference)

__all__ = ["attention", "expert_ffn", "expert_ffn_reference",
           "expert_route", "expert_row_plan", "flash_attention", "mask_to_bias", "pad_head_dim",
           "paged_decode_attention", "paged_decode_attention_reference",
           "paged_decode_write_attention", "paged_gather",
           "paged_head_rows", "paged_heads_a_row", "paged_pool_rows",
           "paged_pool_shape", "paged_pool_width", "paged_route",
           "paged_verify_attention",
           "paged_verify_attention_reference", "paged_walk_blocks",
           "paged_walk_extent", "reset_route_log",
           "route_log", "ssm_route", "ssm_step", "ssm_step_reference",
           "softmax_with_sink", "trace_mesh", "xla_attention"]
