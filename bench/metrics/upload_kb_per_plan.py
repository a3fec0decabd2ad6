"""Kilobytes (1000 bytes) sent to the device per plan: the change of the
program's counter ``engine.window.upload_bytes`` (the bytes of every array
passed to the fused window program) over the traced window, divided by
the plans completed in it.  ``None`` where the program keeps no such
counter."""


def read(run):
    sent = run.counters.get("engine.window.upload_bytes", 0)
    if not run.plans or not sent:
        return None
    return sent / run.plans / 1e3
