"""Traffic kind ``horizons_build``: a project of machines whose model is a
sequence backbone trained on two horizons (the next row by its main head,
the row after next by a multi-token-prediction module), through
``build_project``, one machine a chunk.

The drive is ``sequence_build``'s, which is ``fleet_build``'s (one
``build_project`` call, a warm-up machine ends set-up, completions counted
from outside, the last machine never counted, the build runs to its end),
and so are the project document, the reading back of a pack, the numbers
held against limits and ``judge``.

**A completion here is a machine's result assembled on the host and handed
to the writer** (``gordo_build_pipeline_chunks_total{path="pipelined"}``,
which the drive counts as it hands a chunk over), not its pack written, as
in the other two kinds.  A pack of this configuration is 2.5 GB and one
leaves every five to six seconds; the sandbox's disk takes about 15 GB at
2 GB/s and 0.2 GB/s after, whoever shares it, so counted at the write the
rate read 429-542 models/h on one machine in one call and 313 where the disk
began slow (PERF.md section 6, PR 34; REVIEW.md asked for this repair).  The
packs are written all the same: the build runs to its end, every one of
``check.machines`` is read back and compared, and ``glm.write_s_per_model``
reads the writes.  ``kinds/sequence_build.py`` names its
reference (``reference/kimi_linear.py``) and its work count
(``backbone_work.py``, which reads ``linear_attn_config``) inside ``run``,
``reference_of`` and ``compare``, and this PR may edit no file of the
benchmark: those three are written again here around
``reference/glm_moe_lite.py`` and ``latent_work.py``, with ``check``, which
calls them.  ``run`` and ``compare`` differ from ``sequence_build``'s in
those names alone (PERF.md section 7 asks a ``benchmark`` issue to make the
reference and the work count arguments of one drive).
"""

from __future__ import annotations

import concurrent.futures
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

HANDOFFS_SERIES = "gordo_build_pipeline_chunks_total"
HANDOFFS_LABEL = "pipelined"


def _handed() -> float:
    """Chunks whose results the drive has assembled and handed to the writer."""
    from gordo_tpu import telemetry

    series = telemetry.REGISTRY.get(HANDOFFS_SERIES)
    return float(series.value(HANDOFFS_LABEL)) if series is not None else 0.0


def run(ctx) -> Dict[str, Any]:
    """Drive one run; returns the run record the harness reduces."""
    import jax
    from benchmark import latent_work
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
        f"horizons_build: {n_chunks} chunks of {chunk} machines "
        f"({SETUP_CHUNKS} set-up, up to {countable} counted, the last never), "
        f"{config['dataset']['rows']} rows x {config['dataset']['n_tags']} tags, "
        f"sequences of {config['model']['context']} at stride {config['model']['stride']}, "
        f"the row after next trained at weight {config['model']['mtp_weight']}"
    )

    holder: Dict[str, Any] = {}
    aot_before = int(compile_plane.aot_fallbacks())
    handed_before = _handed()

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
            if _handed() - handed_before >= count:
                return time.time()
            if not thread.is_alive():
                return time.time() if _handed() - handed_before >= count else None
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
            # (from build start): a pack of 2.5 GB is written and flushed on
            # one writer thread, behind the hand-over that the window counts
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
            "work_per_chunk": latent_work.chunk_work(config, chunk),
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
    model, on the device; ``history``: the trained loss ``L1 + lambda L2``)
    and, with ``folds``, the thresholds from its cross-validation, which
    read the main head alone.  ``quantize`` and ``fault`` are the control's."""
    from benchmark.reference import glm_moe_lite

    out = glm_moe_lite.fit(np.asarray(rows), config["model"], seed,
                           quantize=quantize, fault=fault)
    out["params"], out["seed"] = out.pop("model"), seed
    if folds:
        out["thresholds"] = glm_moe_lite.cross_validate(
            np.asarray(rows), config["model"], seed,
            int(config["cv"]["n_splits"]), quantize=quantize, fault=fault)
    return out


def compare(made: Dict[str, Any], ref: Dict[str, Any],
            log=lambda message: None) -> Dict[str, float]:
    """The numbers held against limits, for one machine (``made``: what the
    timed path wrote, or a control's fit in its place; ``ref``: the
    reference's fit).  First and last epoch's loss: the relative gap.

    ``update_norm_gap`` is the larger of two readings over the parameters of
    every layer, each relative to the larger of the reference's change of
    that parameter from the common start and the median parameter's:

    - the WORST parameter's gap between the norms of the two fits' changes
      (``fleet_build``'s number): a parameter left at its start reads 1;
    - the MEDIAN parameter's distance between the two changes themselves.
      Adam moves every parameter by about the learning rate a step whatever
      the gradient, so a fit on the wrong data moves the norms little; the
      distance sees the direction.  The median and not the worst: a routed
      expert's matrices are trained by the few positions routed to it, a
      routing near-tie that bfloat16 flips changes which, and the worst
      parameter's distance reads 0.5 to 1.3 on sound runs (PERF.md section 4).

    The worst threshold's gap, and a count of non-finite values."""
    from benchmark.reference import glm_moe_lite

    h_made = np.asarray(made["history"], np.float64)
    h_ref = np.asarray(ref["history"], np.float64)
    numbers = {
        "loss_first_gap": abs(h_made[0] - h_ref[0]) / abs(h_ref[0]),
        "loss_last_gap": abs(h_made[-1] - h_ref[-1]) / abs(h_ref[-1]),
    }
    d = glm_moe_lite.distances(ref["params"], made["params"], ref["seed"], ref["shape"])
    moved_ref, moved_made, apart = (
        np.asarray(d[k], np.float64) for k in ("moved_ours", "moved_theirs", "apart"))
    scale = np.maximum(moved_ref, float(np.median(moved_ref)))
    norms, apart = np.abs(moved_made - moved_ref) / scale, apart / scale
    worst = lambda z: int(np.argmax(np.where(np.isfinite(z), z, np.inf)))  # noqa: E731
    at, far = worst(norms), worst(apart)
    numbers["update_norm_gap"] = float(max(norms[at], np.median(apart)))
    log(f"update_norm_gap {numbers['update_norm_gap']:.4g}: the norms of the changes "
        f"differ by {norms[at]:.4g} at {d['names'][at]} (moved {moved_made[at]:.4g}, "
        f"reference {moved_ref[at]:.4g}, median parameter {np.median(moved_ref):.4g}); "
        f"the median parameter's changes are {np.median(apart):.4g} apart, the worst's "
        f"{apart[far]:.4g} at {d['names'][far]}")
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
