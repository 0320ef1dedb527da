"""Device idle share (%) of the traced window in the chat cell; moves
``itl_p95_ms`` (``chipbench.trace.device_idle``)."""

from chipbench.trace import device_idle as read  # noqa: F401
