"""Device milliseconds per run of the engine's B=1 prefill program
(``_prefill_argmax``), in the traced window."""
from harness.readers import module_mean_ms


def read(run):
    return module_mean_ms(run, "_prefill_argmax")
