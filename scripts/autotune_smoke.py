#!/usr/bin/env python
"""Closed-loop autotuning CPU smoke (round 21, wired into scripts/check.sh).

Runs the bench's ``tuning`` rung end to end on a tiny store and asserts
the ISSUE's acceptance gates outside pytest:

* the offline tuner serves >= 3 windows against a calibrated synthetic
  SLO and CONVERGES on an operating point that meets it — every proposal
  carries a diagnosis (zero undiagnosed), zero ``unknown`` diagnoses on
  healthy windows, zero structurally invalid explain records;
* the emitted operating point round-trips from disk
  (``results/operating_point.json``) with tuner provenance stamped;
* the induced load spike is absorbed by the burn-rate controller
  (>= 1 action, knobs restored to the tuned point, final burn states
  inside the error budget — ``spike_budget_burn == 0``) with ZERO scan
  recompiles, zero unexplained retraces, zero unclassified verdicts and
  zero deadline misses;
* the episode is reconstructible from the flight recording alone: every
  controller action lands as a structurally complete ``tuning.action``
  event on the window timeline, and the v6 obs report's ``tuning``
  section validates.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from raft_tpu import obs  # noqa: E402


def main():
    obs.enable()
    import bench

    out = bench._autotune_rung(tiny=True)
    assert "error" not in out, f"tuning rung failed: {out.get('error')}"

    # -- offline loop: converge on an SLO-meeting, fully diagnosed point --
    tuner = out["tuner"]
    assert tuner["windows"] >= 3, f"only {tuner['windows']} tuner windows"
    assert tuner["converged"], "tuner did not converge"
    assert out["meets_slo"], "emitted operating point misses the SLO"
    assert out["unexplained_diagnoses"] == 0, \
        f"{out['unexplained_diagnoses']} unknown diagnoses"
    assert out["explain_invalid"] == 0, \
        f"{out['explain_invalid']} invalid explain records"
    assert out["proposals_undiagnosed"] == 0, \
        f"{out['proposals_undiagnosed']} proposals without a diagnosis"
    assert out["frontier_points"] >= 1, "empty Pareto frontier"
    assert out["tuned_by"] == "raft_tpu.tuning.autotune", \
        "operating point lost its provenance on the disk round-trip"

    # -- online loop: the spike absorbed inside the error budget ----------
    assert out["calm_actions"] == 0, \
        f"controller acted {out['calm_actions']}x on calm traffic"
    assert out["controller_actions"] >= 1, \
        "the induced spike never drove a controller action"
    assert out["knobs_restored"], "knobs not restored to the tuned point"
    assert out["spike_budget_burn"] == 0, \
        f"SLOs still in breach after recovery: {out['final_slo']}"
    assert out["recompiles_during_spike"] == 0, \
        f"{out['recompiles_during_spike']} scan recompiles during spike"
    assert out["unexplained_retraces"] == 0, \
        f"{out['unexplained_retraces']} unexplained retraces"
    assert out["unclassified"] == 0, \
        f"{out['unclassified']} unclassified request verdicts"
    assert out["spike_deadline_misses"] == 0, \
        f"{out['spike_deadline_misses']} deadline misses"
    assert out["controller_failures"] == 0, \
        f"{out['controller_failures']} controller tick failures"

    # -- reconstructible episode ------------------------------------------
    assert out["tuning_action_events"] >= out["controller_actions"] >= 1, \
        "controller actions missing from the flight recording"
    assert out["tuning_action_events_invalid"] == 0, \
        f"{out['tuning_action_events_invalid']} malformed tuning.action " \
        f"events"
    assert out["report_tuning_problems"] == [], \
        f"v6 tuning section invalid: {out['report_tuning_problems']}"

    print(f"autotune smoke: OK (windows={tuner['windows']} "
          f"moves={tuner['moves']} tuned={out['tuned_knobs']} "
          f"tuned_qps={out['tuned_qps']} tuned_recall={out['tuned_recall']} "
          f"spike_actions={out['controller_actions']} "
          f"budget_burn={out['spike_budget_burn']} "
          f"recompiles={out['recompiles_during_spike']})")


if __name__ == "__main__":
    main()
