"""Work counts and the chip's peaks: the yardstick of roofline shares."""

from .peaks import peaks_for
from .probe import least_bytes

__all__ = ["least_bytes", "peaks_for"]
