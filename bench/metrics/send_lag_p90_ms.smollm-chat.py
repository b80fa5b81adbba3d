"""send_lag_p90_ms.smollm-chat: how late the load generator sent the requests due in the window, 90th percentile (``readers.send_lag_p90_ms``)."""
from readers import send_lag_p90_ms as read  # noqa: F401
