"""Idle share of the device over the traced window of a simulator cell:
1 minus the union of device-op intervals over the window, averaged over
the chips. Moves `sim_client_s_per_s`."""
from bench import trace


def read(m):
    return trace.idle_percent(m.busy_s, m.window_s)
