"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device op intervals / traced window)."""

from benchlib.readings import idle_share as read  # noqa: F401
