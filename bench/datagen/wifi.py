"""UCI-WiFi-shaped tables (QUIP paper, section 7.1): users, wifi, occupancy.

A copy of the shapes of ``repro.data.synthetic.wifi_dataset``, kept here so
that the yardstick cannot move with the program.  Two departures, both
stated in the configuration file:

* every attribute misses exactly ``round(rate * rows)`` cells (``masks``),
  so that each seed gives the imputer the same reference-row counts and
  the device the same compiled shapes;
* ``users.email`` codes are a seeded permutation of ``users.name`` codes.
  With equal columns every user's k-th neighbour was an exact tie (rows
  i - j and i + j), which any floating-point distance breaks arbitrarily.

Tables are plain dicts: ``{"columns": [(name, kind)], "cols": {name: array},
"missing": {name: bool array}}``; missing cells hold 0.
"""

from __future__ import annotations

import numpy as np

from masks import exact_mask

__all__ = ["make"]


def _table(cols: dict, rates: dict, rng) -> dict:
    miss = {}
    for name in cols:
        miss[name] = m = exact_mask(rng, len(cols[name]), rates.get(name, 0.0))
        cols[name] = np.where(m, 0, cols[name]).astype(np.int64)
    return {"columns": [(c, "int") for c in cols], "cols": cols,
            "missing": miss}


def make(rng: np.random.Generator, p: dict) -> dict:
    n_users, n_wifi, n_occ = p["n_users"], p["n_wifi"], p["n_occ"]
    n_rooms = p["n_rooms"]
    rates = p["missing_rates"]
    n_devices = n_users * p["devices_per_user"]
    device_pool = np.arange(1, n_devices + 1, dtype=np.int64)
    tables = {}

    tables["users"] = _table({
        "users.name": np.arange(n_users, dtype=np.int64),
        "users.mac_addr": device_pool[:n_users].copy(),
        "users.email": rng.permutation(n_users).astype(np.int64),
        "users.group": rng.integers(0, 12, n_users).astype(np.int64),
    }, rates, rng)

    start = rng.integers(0, 720, n_wifi).astype(np.int64)
    dur = rng.integers(1, 180, n_wifi).astype(np.int64)
    lid = rng.integers(1, n_rooms + 1, n_wifi).astype(np.int64)
    # device visits follow per-device room preferences
    mac = device_pool[rng.integers(0, n_devices, n_wifi)]
    pref = rng.integers(1, n_rooms + 1, n_devices + 1).astype(np.int64)
    lid = np.where(rng.random(n_wifi) < 0.6, pref[mac], lid)
    tables["wifi"] = _table({
        "wifi.start_time": start,
        "wifi.end_time": start + dur,
        "wifi.lid": lid,
        "wifi.duration": dur,
        "wifi.mac_addr": mac,
    }, rates, rng)

    # occupancy covers the sensored half of the rooms
    o_lid = rng.integers(1, n_rooms // 2 + 1, n_occ).astype(np.int64)
    o_start = rng.integers(0, 720, n_occ).astype(np.int64)
    occ = np.maximum(
        0, (20 - np.abs(o_lid - 30)) + rng.integers(0, 8, n_occ))
    tables["occupancy"] = _table({
        "occupancy.lid": o_lid,
        "occupancy.start_time": o_start,
        "occupancy.end_time": o_start + rng.integers(1, 60, n_occ),
        "occupancy.occupancy": occ.astype(np.int64),
        "occupancy.type": (o_lid % 5).astype(np.int64),
    }, rates, rng)
    return tables
