from .kernel import pq_adc_gather_topk_pallas, pq_adc_topk_pallas
from .lut import LUT_DTYPES, dequantize_lut, lut_error_bound, quantize_lut
from .ops import pq_adc_gather_topk, pq_adc_topk, pq_adc_topk_global
from .ref import (pq_adc_gather_scores_onehot, pq_adc_gather_scores_ref,
                  pq_adc_gather_topk_ref, pq_adc_scores_ref, pq_adc_topk_ref)

__all__ = [
    "pq_adc_topk_pallas", "pq_adc_gather_topk_pallas",
    "pq_adc_topk", "pq_adc_gather_topk", "pq_adc_topk_global",
    "pq_adc_scores_ref", "pq_adc_topk_ref",
    "pq_adc_gather_scores_ref", "pq_adc_gather_scores_onehot",
    "pq_adc_gather_topk_ref",
    "LUT_DTYPES", "quantize_lut", "dequantize_lut", "lut_error_bound",
]
