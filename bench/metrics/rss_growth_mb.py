"""How far the process's resident set rose inside the window: its largest
read there less its read when the window opened, in MB.  Loader or buffer
growth over the window shows here even where the runtime's share hides it
in ``host_rss_peak_mb``."""


def read(w):
    return (w.rss_peak_bytes - w.rss_start_bytes) / 1e6
