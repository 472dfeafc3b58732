"""Scheduler round: median host time between consecutive round hooks
(``AsyncFrontend.round_hook``) inside the window."""

from benchlib.readings import round_interval_ms as read  # noqa: F401
