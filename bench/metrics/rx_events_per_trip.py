"""Beacon deliveries a BEACON_RX trip of the event loop retires: the
``beacons_rx`` of every lane of every group of the window's grids over
their ``rx_trips`` (the trips whose popped root is a delivery).  Nothing
where the program keeps no ``rx_trips`` leaf or popped no delivery."""
import numpy as np


def read(run):
    groups = [g for grid in run.grids for g in grid["groups"]]
    if not groups or any("rx_trips" not in g["state"] for g in groups):
        return None
    rx = sum(int(np.sum(g["state"]["beacons_rx"])) for g in groups)
    trips = sum(int(np.sum(g["state"]["rx_trips"])) for g in groups)
    return rx / trips if trips else None
