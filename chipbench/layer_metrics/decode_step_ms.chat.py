"""decode_step_ms.* (ms): device time of one decode step, from the
decode-chunk program's time in the trace."""

from chipbench.readers import decode_step_ms as read  # noqa: F401
