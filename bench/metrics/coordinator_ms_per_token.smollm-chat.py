"""coordinator_ms_per_token.smollm-chat: host time of the runtime's steps outside engine calls, per confirmed token (``readers.coordinator_ms_per_token``)."""
from readers import coordinator_ms_per_token as read  # noqa: F401
