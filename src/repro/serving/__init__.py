"""Paged KV-cache serving subsystem: host-side block-pool allocator.

The device-side pieces live next to their peers: the paged arena init in
``models.transformer.init_paged_cache``, the page-view attention in
``models.layers``, the Pallas decode kernel in ``kernels.paged_attention``,
and the chunked-prefill scheduler integration in ``launch.scheduler``.
"""
from .kvcache import BlockPool, PoolExhausted, ring_pages

__all__ = ["BlockPool", "PoolExhausted", "ring_pages"]
