"""Host utilities: timing, leveled logging.

Reference parity: ``include/Timer.h`` (ns timer, spin-sleep, per-loop
print) and ``include/Debug.h`` / ``src/Debug.cpp`` (printf-style leveled
logging with ANSI colors, compile-time gates).  Spans and XLA device
traces live in the observability plane (``sherman_tpu.obs``; the
reference has no tracer, SURVEY.md §5).
"""

from __future__ import annotations

from sherman_tpu.utils.debug import (DEBUG, ERROR, INFO, debug_item,
                                     notify_error, notify_info, set_level)
from sherman_tpu.utils.timer import Timer, spin_sleep_ns

__all__ = [
    "Timer", "spin_sleep_ns",
    "notify_info", "notify_error", "debug_item", "set_level",
    "INFO", "ERROR", "DEBUG",
]
