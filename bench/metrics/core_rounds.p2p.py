"""Core search: mean rounds of the bellman core search per P2P batch,
from the ``core_rounds`` tag of the engine's phase spans (the last
round changes nothing)."""
from yardstick.phases import core_rounds as read  # noqa: F401
