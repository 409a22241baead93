"""Host microseconds per ``dart.wait`` span: the host blocked until a
flushed dispatch's outputs are ready (``Handle.wait`` and
``dart_waitall``), read from the program's own span totals over the
traced stretch."""

from dartbench import program


def read(run):
    return program.mean_us(run, "dart.wait")
