"""Device milliseconds per run of the engine's decode step program
(``_decode_argmax``), in the traced window of the chat cell."""
from harness.readers import module_mean_ms


def read(run):
    return module_mean_ms(run, "_decode_argmax")
