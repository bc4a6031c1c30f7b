"""What the readers of the two state-space kernels share.  Not a metric: no
``BENCHMARK.json`` entry names this file.

The kernels are rows of the trace summary's ``ops`` table under the names
``ray_tpu/ops/ssd.py`` pins (``KERNEL_SSD_CHUNK_FWD``,
``KERNEL_SSD_RECURRENT_STEP``; spelled out here, not imported: these files
also run over a parent commit whose program has neither).  Seconds, the
share of a roofline and the counters' growth are ``_gdn.py``'s helpers: a
trace without the kernel, a kind without the counts or a span without the
counter gives ``None``."""

from benchmark.layer_metrics._gdn import (kernel_seconds, per, roofline,
                                          span_delta)

CHUNK_FWD = "ssd_chunk_fwd"
RECURRENT_STEP = "ssd_recurrent_step"
MOE_GMM = "moe_gmm"

__all__ = ["CHUNK_FWD", "RECURRENT_STEP", "MOE_GMM", "kernel_seconds",
           "roofline", "span_delta", "per"]
