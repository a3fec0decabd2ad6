"""Share of the fused window programs' pool rows that hold a real
candidate: the change of the program's counter ``engine.window.rows_real``
over the traced window, divided by that of ``engine.window.rows_padded``
(each model's candidates padded to the bucket of the window's largest
batch, ``n_pad`` rows per model).  The rest is padding the device program
carries.  ``None`` where the program keeps neither counter."""


def read(run):
    padded = run.counters.get("engine.window.rows_padded", 0)
    if not padded:
        return None
    return 100.0 * run.counters.get("engine.window.rows_real", 0) / padded
