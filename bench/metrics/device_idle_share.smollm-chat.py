"""device_idle_share.smollm-chat: share of the traced window with no operation on the device (``readers.device_idle_share``)."""
from readers import device_idle_share as read  # noqa: F401
