"""Device-idle milliseconds per decode step while the engine's host waits
for the step's tokens (its ``engine.sync`` span), in the traced window of
the chat cell."""
from harness.readers import idle_under_ms


def read(run):
    return idle_under_ms(run, "engine.sync")
