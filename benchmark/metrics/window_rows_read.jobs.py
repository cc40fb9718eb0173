"""Window cache: cache rows the window layers' decode steps fetch over
the rows they need, in the window (the program's
`lm_server_decode_kv_rows_total{layers=window}` counters, kind read over
kind live: one window layer, every chunk dispatch's steps; a window layer
needs min(length, window) rows a slot).

What it is: the program's own reckoning on the host (`LMServer._kv_rows`:
the ring's rows R, the kernel's block rows and the slots' lengths), not
bytes the device was seen to fetch. So it is a GUARD on the ring's size
and the block size: R grown past the window, blocks that do not divide it,
or a window layer given `max_len` rows again move it (whole planes would
read ~4.5 at this cell's lengths); a kernel that fetched more than
`_kv_rows` reckons would not, and `window_decode_roofline.jobs` (device
time) is what shows that. Under this cell's traffic every prompt is at
least the window long and the blocks divide the ring, so it reads 1.000 on
every run. Nothing where the program has no such counter (a program
without window layers)."""


def read(run):
    c = run["counters"]
    if "end" not in c or "kv_rows_window_live" not in c["end"]:
        return None
    live = c["end"]["kv_rows_window_live"] - c["start"]["kv_rows_window_live"]
    read_ = c["end"]["kv_rows_window_read"] - c["start"]["kv_rows_window_read"]
    return read_ / live if live else None
