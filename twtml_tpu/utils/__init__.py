from .rounding import round_half_up
from .logging import get_logger
from .clock import now_ms, now_s
from .backend import (
    force_virtual_cpu_devices,
    set_cpu_device_count_hint,
)

__all__ = [
    "round_half_up",
    "get_logger",
    "now_ms",
    "now_s",
    "force_virtual_cpu_devices",
    "set_cpu_device_count_hint",
]
