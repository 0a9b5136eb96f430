"""The process's largest resident set read inside the window
(``/proc/self/statm`` every 50 ms from the window's start to its end), in
MB.  Set-up's own peak is not in it."""


def read(w):
    return w.rss_peak_bytes / 1e6
