from repro.kernels.attention.attention import (flash_attention_pallas,
                                               paged_flash_decode_pallas,
                                               paged_flash_prefill_pallas,
                                               paged_latent_decode_pallas,
                                               paged_latent_prefill_pallas)
from repro.kernels.attention.ops import (gather_kv_pages, paged_attention,
                                         paged_latent_attention)
from repro.kernels.attention.ref import (attention_ref, paged_attention_ref,
                                         paged_latent_attention_ref,
                                         paged_latent_prefill_ref,
                                         paged_latent_verify_ref,
                                         paged_prefill_ref,
                                         paged_verify_ref)

__all__ = [
    "flash_attention_pallas", "paged_flash_decode_pallas",
    "paged_flash_prefill_pallas", "paged_latent_decode_pallas",
    "paged_latent_prefill_pallas",
    "gather_kv_pages", "paged_attention", "paged_latent_attention",
    "attention_ref", "paged_attention_ref", "paged_latent_attention_ref",
    "paged_latent_prefill_ref", "paged_latent_verify_ref",
    "paged_prefill_ref", "paged_verify_ref",
]
