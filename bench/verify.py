"""The comparison that decides ``correct``.

Every answer the window produced and the sample kept is compared with the
configuration's plain reference, run once the window has closed:

* ``grid_gap``: the largest ``|program - reference|`` over every point of
  every sampled grid, the reference advanced from the same input by the
  sweeps the program reports it ran;
* ``stop_res_over_tol``: for answers that report convergence, the
  reference's residual at that sweep count over the request's ``tol``
  (the program stopped no earlier than the tolerance allows);
* ``flag_errors``: answers whose convergence flag disagrees with the
  reference's residual;
* ``missing``: requests due in the window that never came back.

Each number has its limit in the configuration's file; ``correct`` holds
when every number is at or under its limit.
"""
from __future__ import annotations

import importlib

import jax
import numpy as np


def reference_module(config: dict):
    """The configuration's plain reference, found by the name it gives."""
    return importlib.import_module(f"bench.references.{config['reference']}")


def _on(x, device):
    """``x`` as a plain array on ``device``; a grid replicated over a mesh
    gives its copy on the first chip."""
    if isinstance(x, jax.Array) and len(x.sharding.device_set) > 1:
        x = x.addressable_shards[0].data
    return jax.device_put(x, device)


def numbers(answers: list[dict], config: dict, device, missing: int
            ) -> dict[str, float]:
    """What the comparison reads, from the program's ``answers``."""
    ref = reference_module(config)
    taps = ref.taps_of(config)
    out = {"grid_gap": 0.0, "missing": float(missing)}
    has_tol = any(a.get("tol") is not None for a in answers)
    if has_tol:
        out["stop_res_over_tol"] = 0.0
        out["flag_errors"] = 0.0
    for a in answers:
        want = ref.sweeps(_on(a["input"], device), int(a["sweeps"]),
                          taps=taps)
        got = _on(a["output"], device).astype(np.float32)
        gap = float(jax.numpy.max(abs(got - want)))
        out["grid_gap"] = max(out["grid_gap"],
                              gap if np.isfinite(gap) else np.inf)
        if a.get("tol") is None:
            continue
        res = float(ref.residual(want, taps=taps))
        if a["converged"]:
            out["stop_res_over_tol"] = max(out["stop_res_over_tol"],
                                           res / a["tol"])
        if bool(a["converged"]) != (res <= a["tol"]):
            out["flag_errors"] += 1
    return out


def control_answers(answers: list[dict], config: dict, device) -> list:
    """The control: the reference, in the precision one step below the
    configuration's, put in the program's place on the same inputs."""
    ref = reference_module(config)
    taps = ref.taps_of(config)
    low = config["control_dtype"]
    made = []
    for a in answers:
        u = _on(a["input"], device)
        if a.get("tol") is None:
            made.append(dict(a, output=ref.sweeps(u, int(a["sweeps"]),
                                                  taps=taps, compute=low)))
            continue
        cadence = config["check_every"]
        v, n, res = ref.solve_to_tol(u, a["tol"], a["max_iters"] // cadence,
                                     taps=taps, cadence=cadence,
                                     compute=low)
        made.append(dict(a, output=v, sweeps=int(n),
                         converged=float(res) <= a["tol"]))
    return made


def judge(nums: dict[str, float], limits: dict[str, float]
          ) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every number read."""
    table = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    return all(v <= limits[k] for k, v in nums.items()), table
