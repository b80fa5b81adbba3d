"""prefill_mfu.smollm-chat: operations the prefill chunks need over their host time, as a share of the bf16 peak (``readers.prefill_mfu``)."""
from readers import prefill_mfu as read  # noqa: F401
