"""stream_lag_p90_ms.smollm-chat: a token's confirmation to its SSE chunk's flush, 90th percentile over tokens confirmed in the window (``program_spans.stream_lag_p90_ms``)."""
from program_spans import stream_lag_p90_ms as read  # noqa: F401
