"""Host microseconds per ``dart.coerce`` span: the front end turning a
put's host payload into a device array of the ref's dtype and shape
(``GlobalRef._coerce``, one host->device copy), read from the program's
own span totals over the traced stretch."""

from dartbench import program


def read(run):
    return program.mean_us(run, "dart.coerce")
