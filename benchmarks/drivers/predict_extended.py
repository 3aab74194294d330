"""Driver ``predict_extended``: ``drivers/predict.py``'s loop on a sky
that is not points (``-a 1 -p -F 1``).

The loop, the cycling ``SimMS``, the kept rows and the three comparisons
are ``drivers/predict.py``'s own, imported: ``run`` and ``check`` there
read the observation from ``run.obs``, which this driver replaces with
``reference_extended.Observation`` (the same array, uvw, Jones, noise
and solutions; Gaussian, disk, ring and shapelet sources with three-term
spectra catalogued at another frequency than the one observed).  The sky
text and the cluster file are then written by ``datagen.write_sky`` from
that observation, and the shapelet sources' ``<name>.fits.modes`` files
here, beside them in the run's work directory, which is where the
program's reader looks for them.

On the first checked cycle of every run ``check`` prints a ``[control]``
line: the reference's own numbers in the program's place under three
falsifications (every source a point; the shapelet sources left out; the
spectrum taken at ``f0``), each as ``model_vs_reference`` would read it,
and under a fourth that is reported whichever way it falls: the flux law
by the rule the program has at parse (scaled where ANY spectral term is
not zero) where the per-channel rule belongs (scaled where ``si`` is
not), which moves one source in sixteen by parts in a thousand.
"""

import os

import numpy as np

import harness
import reference
import reference_extended

import scopes

predict = harness.load_module("drivers", "predict")

# ``scopes.scope_path`` takes an operation's second level from a fixed
# list of names, which a file that is there holds and this PR may not
# edit: ``shapelet`` (``rime/envelopes.shapelet``'s scope, PR 51) joins it
# here, when the cell's driver is loaded and before any trace is read, so
# that the ``[scope]`` table has its ``rime/phasor/shapelet`` row and
# ``shapelet_dev_ms.ext`` its seconds.  PERF.md section 7 asks the next
# ``benchmark`` issue for the name in ``scopes.SECOND`` itself.
if "shapelet" not in scopes.SECOND:
    scopes.SECOND = scopes.SECOND + ("shapelet",)

#: the falsifications of the ``[control]`` line: what it says, and the
#: keyword of ``reference_extended.coherencies`` that makes it
CONTROLS = (("every source a point", "points"),
            ("the shapelet sources left out", "no_shapelets"),
            ("the spectrum taken at f0", "at_f0"),
            ("(reported) the flux law by the parse rule", "any_term"))


def observation(run):
    """The extended observation of this run, in ``run.obs``'s place."""
    if not isinstance(run.obs, reference_extended.Observation):
        run.obs = reference_extended.Observation(run.config, run.seed)
    return run.obs


def write_modes(obs, out_dir: str):
    """``<name>.fits.modes`` of every shapelet source, beside the sky
    file that ``datagen.write_sky`` writes into ``out_dir``."""
    paths = []
    for name, text in obs.modes.items():
        paths.append(os.path.join(out_dir, name + ".fits.modes"))
        with open(paths[-1], "w") as f:
            f.write(text)
    return paths


def run(run):
    write_modes(observation(run), run.work)
    return predict.run(run)


def falsified(run, k):
    """{control's keyword: rms(model_ref under it - model_ref) /
    rms(model_ref)} on the seeded kept rows of cycle ``k``."""
    obs = observation(run)
    i = k % int(run.config["n_tiles_on_disk"])
    n = int(run.traffic["check_rows_per_cycle"])
    rows = predict.kept_rows(run, k)[:n]
    given = reference.read_solutions(run.sol_path)[i]
    v_ref = obs.model(i, given, rows=rows)
    return {key: reference.rms(obs.model(i, given, rows=rows, **{key: True})
                               - v_ref) / reference.rms(v_ref)
            for _, key in CONTROLS}


def check(run):
    """``drivers/predict.py``'s three comparisons against
    ``reference_extended.Observation.model``, and the ``[control]``
    line."""
    obs = observation(run)
    checks = predict.check(run)
    cycles = run.window.tiles
    if cycles:
        got = falsified(run, cycles[0])
        kinds = ", ".join(f"{n} {k}" for k, n in obs.sky.counts().items())
        # a line of its own: limits.py prints no notes
        print(f"[control] seed {run.seed}, cycle {cycles[0]}, the "
              f"reference's own model in the program's place ({kinds}; "
              f"f0 {np.unique(obs.sky.f0)[0] * 1e-6:g} MHz at "
              f"{obs.freq * 1e-6:g} MHz): " + "; ".join(
                  f"{said} {got[key]:.4g}" for said, key in CONTROLS),
              flush=True)
    return checks
