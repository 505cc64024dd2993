"""90th percentile of time to first token, from when each request was
DUE (not from when the generator got round to sending it), over every
request of the window."""
from chiplib.common import request_quantile


def read(obs):
    return request_quantile(obs, "open", "ttft_ms", 0.9)
