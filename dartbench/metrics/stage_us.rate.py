"""Host microseconds per ``dart.stage`` span: the engine staging a put's
payload as host bytes at enqueue (``_to_host_bytes``, a device->host
copy when the front end handed it a device array), read from the
program's own span totals over the traced stretch."""

from dartbench import program


def read(run):
    return program.mean_us(run, "dart.stage")
