"""paged_attention_roofline.batch: least time for the decode kernel's live pages and operations over its device time (``readers.paged_attention_roofline``)."""
from readers import paged_attention_roofline as read  # noqa: F401
