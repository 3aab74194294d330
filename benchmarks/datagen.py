"""An observation of ``reference.Observation`` written as the files the
program reads: a SimMS directory (the program's own on-disk format, so
its own writer), the sky and cluster text files, and for the predict
deployment a solutions file in the upstream text format (written by the
reference: it is the format's other implementation).

Every number written comes from ``reference.py``; the program's code
only lays them out on disk.
"""

from __future__ import annotations

import os

import numpy as np

import reference


def write_sky(obs: reference.Observation, out_dir: str):
    sky = os.path.join(out_dir, "sky.txt")
    with open(sky, "w") as f:
        f.write("\n".join(obs.sky_lines) + "\n")
    with open(sky + ".cluster", "w") as f:
        f.write("\n".join(obs.cluster_lines) + "\n")
    return sky, sky + ".cluster"


def vis_tile(obs: reference.Observation, tile: int, x: np.ndarray):
    """The program's host-side tile container holding ``x`` [B, 2, 2]."""
    from sagecal_tpu.io import dataset as ds
    u, v, w, s1, s2 = obs.geometry(tile)
    t0 = 4.93e9 + tile * obs.tilesz * obs.tdelta
    return ds.VisTile(
        u=u, v=v, w=w, x=x[:, None].astype(np.complex128),
        flags=np.zeros(obs.nrows, np.int8), sta1=s1, sta2=s2,
        freqs=np.asarray([obs.freq]), freq0=obs.freq, fdelta=obs.fdelta,
        tdelta=obs.tdelta, dec0=obs.dec0, ra0=obs.ra0,
        n_stations=obs.n_sta, nbase=obs.nbase, tilesz=obs.tilesz,
        time_mjd=t0 + obs.tdelta * (np.arange(obs.tilesz) + 0.5))


def write_observation(obs: reference.Observation, out_dir: str,
                      n_tiles: int, data: str) -> str:
    """SimMS of ``n_tiles`` tiles at ``out_dir``/obs.ms.  ``data``:
    "calibrate" stores the observed visibilities, "noise" only noise
    (a column that ``-a 1`` replaces)."""
    from sagecal_tpu.io import dataset as ds
    make = obs.data if data == "calibrate" else obs.noise
    path = os.path.join(out_dir, "obs.ms")
    ds.SimMS.create(path, [vis_tile(obs, t, make(t))
                           for t in range(n_tiles)])
    return path


def write_solutions(obs: reference.Observation, out_dir: str,
                    n_intervals: int) -> str:
    path = os.path.join(out_dir, "true.solutions")
    reference.write_solutions(
        path, [obs.jones(k) for k in range(n_intervals)], obs.freq,
        obs.fdelta, obs.tilesz * obs.tdelta / 60.0)
    return path


def read_column(ms_path: str, tile: int, column: str) -> np.ndarray:
    """[B, 2, 2] of one stored column of one tile, read with numpy alone:
    ``x`` is DATA, ``x_corrected_data`` the output column."""
    with np.load(os.path.join(ms_path, f"tile{tile:05d}.npz")) as z:
        return np.asarray(z[column])[:, 0]
