"""Synthetic problems the tests share: an in-memory sky of point sources
and a simulated full-batch observation of it (known Jones, noise 0.01 Jy)."""

import numpy as np

from sagecal_tpu import skymodel
from sagecal_tpu.io import dataset as ds
from sagecal_tpu.rime import predict as rp

SEED = 17


def _point(name, ll, mm, flux):
    nn = np.sqrt(max(1 - ll * ll - mm * mm, 0.0))
    return skymodel.Source(
        name=name, ra=0, dec=0, ll=ll, mm=mm, nn=nn - 1, sI=flux,
        sQ=0.0, sU=0.0, sV=0.0, sI0=flux, sQ0=0, sU0=0, sV0=0,
        spec_idx=0.0, spec_idx1=0.0, spec_idx2=0.0, f0=150e6)


def make_sky(n_clusters, srcs_per_cluster=3, seed=SEED):
    """An in-memory ClusterSky: ``n_clusters`` one-chunk clusters of flat
    point sources within a few degrees of the phase centre."""
    rng = np.random.default_rng(seed)
    srcs, clusters = {}, []
    for m in range(n_clusters):
        names = []
        for s in range(srcs_per_cluster):
            nm = f"P{m}_{s}"
            ll, mm = rng.normal(0, 0.03, 2)
            srcs[nm] = _point(nm, ll, mm, float(1 + 2 * rng.random()))
            names.append(nm)
        clusters.append((m, 1, names))
    return skymodel.build_cluster_sky(srcs, clusters)


def build_fullbatch(dtype, n_stations, n_clusters, tilesz, seed=SEED,
                    n_tiles=1):
    """Returns (sky, dsky, tiles): ``n_tiles`` independent solve intervals
    of one observation at 150 MHz, corrupted by one random Jones draw."""
    sky = make_sky(n_clusters, seed=seed)
    dsky = rp.sky_to_device(sky, dtype)
    Jtrue = ds.random_jones(n_clusters, sky.nchunk, n_stations,
                            seed=seed + 1, scale=0.2)
    tiles = [ds.simulate_dataset(dsky, n_stations=n_stations, tilesz=tilesz,
                                 freqs=np.array([150e6]), ra0=0.1, dec0=0.9,
                                 jones=Jtrue, nchunk=sky.nchunk,
                                 noise_sigma=0.01, seed=seed + 2 + 1000 * t)
             for t in range(n_tiles)]
    return sky, dsky, tiles
