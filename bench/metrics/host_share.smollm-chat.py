"""host_share.smollm-chat: share of the window the runtime loop spends in its steps, less its waits on the device (``program_spans.host_share``)."""
from program_spans import host_share as read  # noqa: F401
