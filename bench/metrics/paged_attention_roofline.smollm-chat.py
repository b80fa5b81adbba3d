"""paged_attention_roofline.smollm-chat: least time for the GQA decode kernel's live pages (logical K/V bytes) and operations over its device time (``readers.paged_attention_roofline``)."""
from readers import paged_attention_roofline as read  # noqa: F401
