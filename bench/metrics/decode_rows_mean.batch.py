"""decode_rows_mean.batch: live rows per decode call (``readers.decode_rows_mean``)."""
from readers import decode_rows_mean as read  # noqa: F401
