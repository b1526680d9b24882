"""Server layer: host time per P2P batch outside the device wait, in
ms: the batch's ``engine.dispatch``, ``engine.readback`` and
``server.resolve`` spans summed."""
from yardstick.phases import server_host_ms as read  # noqa: F401
