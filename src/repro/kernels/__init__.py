"""Pallas TPU kernels for the perf-critical compute of the DxPTA system:
the photonic DDot GEMM simulation (4-bit QAT/serving path) and the DSE
config-grid evaluator. They compile with Mosaic on a TPU and run in
interpret mode on the CPU backend (`backend.resolve_interpret`), where
they are validated against the pure-jnp oracles in ref.py.
"""
from .ops import (ddot_matmul, decode_rows_device, dse_eval_grid,
                  dse_pareto_multi, dse_pareto_multi_factorized,
                  dse_pareto_spans_factorized, dse_search_grid,
                  dse_search_multi, dse_search_multi_factorized,
                  dse_search_spans_factorized, flash_attention,
                  pallas_grid_search, photonic_matmul)
from .ref import (ddot_matmul_ref, dse_eval_ref, dse_pareto_ref,
                  dse_search_ref, flash_attention_ref, quantize4)

__all__ = ["ddot_matmul", "ddot_matmul_ref", "decode_rows_device",
           "dse_eval_grid", "dse_eval_ref", "dse_pareto_multi",
           "dse_pareto_multi_factorized", "dse_pareto_spans_factorized",
           "dse_pareto_ref", "dse_search_grid", "dse_search_multi",
           "dse_search_multi_factorized", "dse_search_spans_factorized",
           "dse_search_ref", "flash_attention", "flash_attention_ref",
           "pallas_grid_search", "photonic_matmul", "quantize4"]
