"""Median over requests of the mean gap between a request's output
tokens: (done - first) / (tokens - 1)."""
from chiplib.common import request_quantile


def read(obs):
    return request_quantile(obs, "open", "tpot_ms", 0.5)
