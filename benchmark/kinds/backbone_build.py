"""Traffic kind ``backbone_build``: a project of machines whose model is a
sequence backbone, through ``build_project``, one machine a chunk, with
everything that names a configuration read from data.

The drive is ``sequence_build``'s and ``horizons_build``'s once more (one
``build_project`` call, a warm-up machine ends set-up, completions counted
from outside, the last machine never counted, the build runs to its end), and
so are the project document, the reading back of a pack, the numbers held
against limits and ``judge``.  What those two kinds name inside ``run``,
``reference_of`` and ``compare`` is read here from the cell's files:

- the configuration's ``check.reference``: the module under
  ``benchmark/reference/`` that fits a machine in plain ``jax.numpy`` (it has
  ``fit``, ``cross_validate``, ``distances``, as ``reference/lfm2_moe.py``);
- the configuration's ``check.work``: the module under ``benchmark/`` whose
  ``chunk_work(config, machines)`` counts a chunk's operations and bytes;
- the traffic's ``completion``: the counter (``series``) and the label values
  (``labels``) whose count is a completion.  ``build-fortnight`` counts a
  machine's result handed to the writer
  (``gordo_build_pipeline_chunks_total{path="pipelined"}``), as
  ``horizons_build`` does and for its reason: a 1.8 GB pack every four to
  five seconds is more than the sandbox's disk sustains (PERF.md section 6,
  PR 34).  The packs are written all the same, and every one of
  ``check.machines`` is read back and compared.

A fourth backbone is therefore a configuration, a traffic file and a
reference, and no fourth kind file; a ``benchmark`` issue can point
``build-series`` and ``build-horizons`` here and delete their two forks
(PERF.md section 7).
"""

from __future__ import annotations

import concurrent.futures
import functools
import importlib
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.kinds.fleet_build import (  # noqa: F401  (the harness reads some)
    POLL_SECONDS, SETUP_CHUNKS, WindowError, _snapshot, _trace_some,
    cleanup, judge, layout, machine_names, middle, model_seed, reference_rows,
    sample_names,
)
from benchmark.kinds.sequence_build import produced, project_doc  # noqa: F401


#: a parameter that moved by less than this share of the reference's change
#: of it counts as stuck at its start
STUCK = 0.1


def _module(package: str, name: str):
    """``benchmark.<package><name>``, a name from a cell's files: letters,
    digits and ``_`` alone, so it can name nothing outside the package."""
    if not name.replace("_", "").isalnum():
        raise ValueError(f"not a module's name: {name!r}")
    return importlib.import_module(f"benchmark.{package}{name}")


def reference_module(config: Dict[str, Any]):
    return _module("reference.", config["check"]["reference"])


def _completed(completion: Dict[str, Any]) -> float:
    """The traffic's completion count: the value of the counter it names
    (0 before the program has made the series)."""
    from gordo_tpu import telemetry

    series = telemetry.REGISTRY.get(completion["series"])
    return float(series.value(*completion["labels"])) if series is not None else 0.0


def run(ctx) -> Dict[str, Any]:
    """Drive one run; returns the run record the harness reduces."""
    import jax
    from gordo_tpu import compile as compile_plane
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    config, traffic = ctx.config, ctx.traffic
    chunk, n_chunks = layout(config)
    countable = n_chunks - SETUP_CHUNKS - 1
    names = machine_names(ctx.seed, n_chunks * chunk)
    doc = project_doc(config, ctx.seed, len(names))
    machines = NormalizedConfig(doc, f"bench-{ctx.seed}").machines
    os.makedirs(ctx.scratch, exist_ok=True)
    out_dir = tempfile.mkdtemp(prefix="build-", dir=ctx.scratch)
    ctx.log(
        f"backbone_build: {n_chunks} chunks of {chunk} machines "
        f"({SETUP_CHUNKS} set-up, up to {countable} counted, the last never), "
        f"{config['dataset']['rows']} rows x {config['dataset']['n_tags']} tags, "
        f"sequences of {config['model']['context']} at stride {config['model']['stride']}, "
        f"{config['model']['batch_size']} a step; a completion is "
        f"{traffic['completion']['series']}{traffic['completion']['labels']}"
    )

    holder: Dict[str, Any] = {}
    aot_before = int(compile_plane.aot_fallbacks())
    completed = functools.partial(_completed, traffic["completion"])
    handed_before = completed()

    def build() -> None:
        try:
            with jax.profiler.TraceAnnotation("bench.build_project"):
                holder["result"] = build_project(
                    machines, out_dir,
                    max_bucket_size=chunk,
                    artifact_format=config["deployment"]["artifact_format"],
                )
        except Exception as exc:  # surfaced by the waiting thread
            holder["error"] = exc

    thread = threading.Thread(target=build, name="bench-build", daemon=True)
    thread.start()

    def wait_for(count: float, deadline: Optional[float]) -> Optional[float]:
        while True:
            if completed() - handed_before >= count:
                return time.time()
            if not thread.is_alive():
                return time.time() if completed() - handed_before >= count else None
            if deadline is not None and time.time() >= deadline:
                return None
            time.sleep(POLL_SECONDS)

    try:
        t0 = wait_for(SETUP_CHUNKS, None)
        if t0 is None:
            raise WindowError(f"the build ended during set-up: {holder.get('error')!r}")
        trace_dir, tracer = None, None
        if ctx.trace:
            trace_dir = os.path.join(out_dir, "trace")
            tracer = threading.Thread(
                target=_trace_some, name="bench-trace",
                args=(trace_dir, float(traffic["trace_seconds"])),
            )
            tracer.start()
        snap_start = _snapshot()
        ctx.log(f"set-up ended {t0 - ctx.t_process:.3f}s after process start")
        completions: List[float] = []
        snap_end = snap_start
        for k in range(countable):
            with jax.profiler.TraceAnnotation("bench.await_chunk"):
                stamp = wait_for(SETUP_CHUNKS + k + 1, t0 + ctx.seconds)
            if stamp is None:
                break
            completions.append(stamp)
            snap_end = _snapshot()
            ctx.log(f"chunk {SETUP_CHUNKS + k} handed over {stamp - t0:.3f}s into the window")
        else:
            ctx.log("every countable chunk was handed over before the window closed: "
                    "the window is cut at the last of them")
        ctx.log(f"window closed with {len(completions)} of {countable} countable "
                "chunks handed over; the build runs on to its end")
        if tracer is not None:
            tracer.join()
        thread.join()
        if "error" in holder:
            raise WindowError(f"build_project raised: {holder['error']!r}")
        for row in holder["result"].timeline:
            # where each machine's seconds went, by the program's own stamps
            # (from build start): a pack is written and flushed on one writer
            # thread, behind the hand-over that the window counts
            spans = {name: [[round(a, 2), round(b, 2)] for a, b in ivs]
                     for name, ivs in row["phases"].items()}
            ready = [round(p["ready"], 2) for p in row.get("programs", ())]
            ctx.log(f"timeline chunk {row.get('chunk')}: program ready {ready}, {spans}")
        if not completions:
            raise WindowError(f"no chunk completed within {ctx.seconds}s of the window")
        summary = holder["result"].summary()
        failed = (
            len(summary["failed"]) + int(summary["single_built"])
            + int(summary["demoted"]["machines"])
            + int(summary["aot_fallbacks"]) - aot_before
        )
        return {
            "t_setup_end": t0,
            "completions": completions,
            "window_seconds": completions[-1] - t0,
            "models": len(completions) * chunk,
            "chunk_machines": chunk,
            "attempted": len(names),
            "failed": failed,
            "snap_start": snap_start,
            "snap_end": snap_end,
            "trace_dir": trace_dir,
            "out_dir": out_dir,
            "project_names": names,
            "work_per_chunk": _module("", config["check"]["work"]).chunk_work(config, chunk),
        }
    except BaseException:
        shutil.rmtree(out_dir, ignore_errors=True)
        raise


# ---------------------------------------------------------------------------
# correctness: the sampled machines against the plain reference, one by one
# ---------------------------------------------------------------------------

def reference_of(config: Dict[str, Any], rows: np.ndarray, seed: int,
                 folds: bool, quantize=None, fault: Optional[str] = None) -> Dict[str, Any]:
    """The reference's final fit of one machine (``params``: its fitted
    model, on the device; ``history``: the trained loss) and, with ``folds``,
    the thresholds from its cross-validation, by the module the
    configuration names.  ``quantize`` and ``fault`` are the control's."""
    reference = reference_module(config)
    out = reference.fit(np.asarray(rows), config["model"], seed,
                        quantize=quantize, fault=fault)
    out["params"], out["seed"] = out.pop("model"), seed
    out["distances"] = reference.distances
    if folds:
        out["thresholds"] = reference.cross_validate(
            np.asarray(rows), config["model"], seed,
            int(config["cv"]["n_splits"]), quantize=quantize, fault=fault)
    return out


def compare(made: Dict[str, Any], ref: Dict[str, Any],
            log=lambda message: None) -> Dict[str, float]:
    """The numbers held against limits, for one machine (``made``: what the
    timed path wrote, or a control's fit in its place; ``ref``: the
    reference's fit, which brings its module's ``distances``).  First and
    last epoch's loss: the relative gap.

    ``update_norm_gap`` is the largest of three readings over the parameters
    of every layer, the first two relative to the larger of the reference's
    change of that parameter from the common start and the median parameter's:

    - the WORST parameter's gap between the norms of the two fits' changes
      (``fleet_build``'s number): a parameter left at its start reads 1;
    - the MEDIAN parameter's distance between the two changes themselves.
      Adam moves every parameter by about the learning rate a step whatever
      the gradient, so a fit on the wrong data moves the norms little; the
      distance sees the direction.  The median and not the worst: a routed
      expert's matrices are trained by the few positions routed to it, a
      routing near-tie that bfloat16 flips changes which, and the worst
      parameter's distance reads 0.5 to 1.3 on sound runs (PERF.md section 4).

    - a parameter that the timed path's fit moved by less than ``STUCK`` (a
      tenth) of what the reference's fit moved it reads its gap relative to
      its OWN reference change, 0.9 to 1, whatever its size.  Adam moves
      every trained parameter by about the learning rate a step, so a sound
      fit has none; a parameter that no gradient reaches (a norm that the
      forward pass leaves out) stays at its start, and where it is a small
      vector the first reading does not see it under the median parameter's
      scale (the heads' norms left out read 0.05 to 0.07 by the two readings
      above, under a sound build's 0.08; my chip run, PR 37).

    The worst threshold's gap, and a count of non-finite values."""
    h_made = np.asarray(made["history"], np.float64)
    h_ref = np.asarray(ref["history"], np.float64)
    numbers = {
        "loss_first_gap": abs(h_made[0] - h_ref[0]) / abs(h_ref[0]),
        "loss_last_gap": abs(h_made[-1] - h_ref[-1]) / abs(h_ref[-1]),
    }
    d = ref["distances"](ref["params"], made["params"], ref["seed"], ref["shape"])
    moved_ref, moved_made, apart = (
        np.asarray(d[k], np.float64) for k in ("moved_ours", "moved_theirs", "apart"))
    scale = np.maximum(moved_ref, float(np.median(moved_ref)))
    norms, apart = np.abs(moved_made - moved_ref) / scale, apart / scale
    worst = lambda z: int(np.argmax(np.where(np.isfinite(z), z, np.inf)))  # noqa: E731
    at, far = worst(norms), worst(apart)
    share = np.where(moved_ref > 0, moved_made / np.maximum(moved_ref, 1e-300), np.inf)
    least = int(np.argmin(share))
    stuck = float(1.0 - share[least]) if share[least] < STUCK else 0.0
    numbers["update_norm_gap"] = float(max(norms[at], np.median(apart), stuck))
    log(f"update_norm_gap {numbers['update_norm_gap']:.4g}: the norms of the changes "
        f"differ by {norms[at]:.4g} at {d['names'][at]} (moved {moved_made[at]:.4g}, "
        f"reference {moved_ref[at]:.4g}, median parameter {np.median(moved_ref):.4g}); "
        f"the median parameter's changes are {np.median(apart):.4g} apart, the worst's "
        f"{apart[far]:.4g} at {d['names'][far]}; the parameter moved least beside the "
        f"reference's change of it is {d['names'][least]}, {share[least]:.4g} of it "
        f"(under {STUCK} it is stuck and reads {1 - share[least]:.4g})")
    nonfinite = int(not np.all(np.isfinite(h_made))) + int(
        np.sum(~np.isfinite(moved_made)) + np.sum(~np.isfinite(apart)))
    t = np.asarray(made["thresholds"], np.float64) if "thresholds" in made else None
    if t is not None:
        nonfinite += int(not (np.all(np.isfinite(t)) and np.all(t > 0)))
    if t is not None and "thresholds" in ref:
        t_ref = np.asarray(ref["thresholds"], np.float64)
        t_gaps = np.abs(t - t_ref) / np.maximum(t_ref, np.median(t_ref))
        numbers["threshold_gap"] = float(np.max(t_gaps))
        log(f"threshold_gap {numbers['threshold_gap']:.4g} at threshold "
            f"{int(np.argmax(t_gaps))} (0 is the aggregate one); "
            f"aggregate {t[0]:.6g}, reference {t_ref[0]:.6g}")
    numbers["nonfinite"] = float(nonfinite)
    return numbers


def check(ctx, record: Dict[str, Any]) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """Compare ``check.machines`` of the project's machines, drawn from the
    seed, with the reference's own fit of each, one machine at a time; for
    the first ``fold_machines`` the reference also runs the folds, and the
    thresholds are compared.  Runs after the build has returned and its
    arrays are freed."""
    config = ctx.config
    spec = config["check"]
    names = sample_names(record["project_names"], ctx.seed, int(spec["machines"]))
    if len(names) < int(spec["machines"]):
        raise WindowError(
            f"the project has {len(names)} machines and check.machines asks for "
            f"{spec['machines']}")
    per_machine = []
    t0 = time.time()
    # the next machine's pack is read from disk while the chip fits this one
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as reader:
        reading = reader.submit(produced, record["out_dir"], names[0])
        for i, name in enumerate(names):
            ref = reference_of(config, reference_rows(config, name),
                               model_seed(ctx.seed), folds=i < int(spec["fold_machines"]))
            made = reading.result()
            if i + 1 < len(names):
                reading = reader.submit(produced, record["out_dir"], names[i + 1])
            per_machine.append(compare(
                made, ref, lambda message, name=name: ctx.log(f"{name}: {message}")))
            del made, ref
    ctx.log(f"reference of {len(names)} machines: {time.time() - t0:.1f}s")
    return judge(middle(per_machine), spec["limits"], ctx.log)
