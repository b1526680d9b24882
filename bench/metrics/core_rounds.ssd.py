"""Core search: mean rounds of the bellman core search per SSD batch,
from the ``core_rounds`` tag of the engine's phase spans (the last
round changes nothing)."""
from yardstick.phases import core_rounds as read  # noqa: F401
