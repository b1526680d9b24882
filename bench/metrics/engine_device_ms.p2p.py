"""Engine layer: device time per run of the jitted P2P program, in ms,
from the profiler trace's program (``XLA Modules``) events, named by
the host dispatch of ``QueryEngine``'s jitted function."""
from yardstick.readers import engine_device_ms as read  # noqa: F401
