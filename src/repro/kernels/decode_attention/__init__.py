from repro.kernels.decode_attention.ops import (
    align_block_k,
    decode_attention,
    paged_decode_attention,
    paged_kv_append,
    paged_latent_append,
    paged_latent_decode,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref,
    gather_pages,
    paged_decode_attention_ref,
    paged_kv_append_ref,
    paged_latent_append_ref,
    paged_latent_decode_ref,
)
