"""Clients the device data pool inserted per round of the window: each is
a client drawn, padded and uploaded by the host.  Nothing when the system
keeps no pool."""
import math


def read(ctx):
    if not ctx["rounds"] or math.isnan(ctx["pool_inserts"]):
        return None
    return ctx["pool_inserts"] / ctx["rounds"]
