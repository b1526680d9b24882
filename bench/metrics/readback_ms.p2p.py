"""Engine layer: mean ``engine.readback`` span per P2P batch, in ms:
the answer copied to the host and put back in original node order."""
from yardstick.phases import readback_ms as read  # noqa: F401
