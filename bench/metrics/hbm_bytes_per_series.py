"""Peak device bytes held on the fullest chip, after the window, per
series held: the allocator's ``peak_bytes_in_use`` plus its
``peak_bytes_reserved`` for compiled programs' temporaries
(``harness.device_peak_bytes``)."""


def read(run):
    return run.memory_peak_bytes / run.rows if run.memory_peak_bytes \
        else None
