"""queue_wait_p90_ms.smollm-chat: submit to first admission, 90th percentile over requests submitted in the window (``program_spans.queue_wait_p90_ms``)."""
from program_spans import queue_wait_p90_ms as read  # noqa: F401
