"""decode_mfu.smollm-chat: the whole decode step's share of the bf16 peak: operations its live rows need over the calls' host time (``readers.decode_mfu``)."""
from readers import decode_mfu as read  # noqa: F401
