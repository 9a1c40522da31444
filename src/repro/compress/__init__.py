"""Cost-aware gradient compression for the multi-cloud hierarchy.

Three codecs — ``topk`` (error-feedback sparsification), ``qsgd``
(unbiased stochastic quantization), ``none`` (fp32 passthrough) — plus a
per-link policy layer that assigns a codec to each edge of the
client → edge → global upload path, so cheap intra-cloud links can stay
uncompressed while expensive cross-cloud egress compresses aggressively.

Hot paths are fused Pallas kernels (repro.kernels.topk_mask / quantize,
compiled on TPU, interpreted on CPU); exact wire bytes feed
repro.core.cost.CostModel.
"""
from repro.compress.base import (Codec, CompressedUpdate, ef_step,
                                 ef_step_masked, make_codec)
from repro.compress.policy import (POLICIES, LinkPolicy, build_link_policy,
                                   policy_from_flcfg)
from repro.compress.qsgd import QSGDCodec
from repro.compress.topk import TopKCodec

__all__ = ["Codec", "CompressedUpdate", "ef_step", "ef_step_masked",
           "make_codec",
           "POLICIES", "LinkPolicy", "build_link_policy",
           "policy_from_flcfg", "QSGDCodec", "TopKCodec"]
