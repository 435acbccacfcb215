from .kernel import paged_decode_attention
from .ops import decode_attention
from .ref import decode_attention_ref

__all__ = ["decode_attention", "decode_attention_ref",
           "paged_decode_attention"]
