"""Device idle share (%) of the traced window in a training cell; moves
``train_tokens_per_s`` (``chipbench.trace.device_idle``)."""

from chipbench.trace import device_idle as read  # noqa: F401
