"""Device milliseconds per run of the decode step (``_decode_argmax``) in
the operations under the model's ``kv_write`` scope: the key/value cache
writes of every layer, in the traced window of the chat cell."""
from harness.readers import scope_ms


def read(run):
    return scope_ms(run, "_decode_argmax", "kv_write")
