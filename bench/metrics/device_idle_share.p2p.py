"""Device: share of the traced window in which no operation ran, in %."""
from yardstick.readers import device_idle_share as read  # noqa: F401
