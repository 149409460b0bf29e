"""Traffic kind ``fleet_build``: a project of machines through
``build_project``, timed between chunk completions.

The generator is general: the configuration file says what one machine is
(model, tags, train window), how large a chunk is and how many machines the
project has; the traffic file says how the project is driven and how many
seconds are traced; ``--seed`` names the machines and their tags and
seeds the models.  A new mix or a new configuration is a new data file.

One chunk is warm-up (it compiles, or loads the compiled program, and ends
set-up).  The window is ``--seconds`` long from that chunk's completion; the
rate is taken from there to the last completion inside it.  A completion is
the chunk's pack being written: ``gordo_build_machines_total{path="fleet"}``
reaching the next multiple of the chunk size, watched from outside the
program.  The project is larger than the window can hold, so the window is
always outlasted; the build then runs to its end (nothing in the program
stops it early) before the window's artifacts are checked.  The project's
last chunk is never counted: on the chip a chunk is written when the NEXT
chunk's program ends, so the last one is written at once.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

COMPLETIONS_SERIES = "gordo_build_machines_total"
COMPLETIONS_LABEL = "fleet"
POLL_SECONDS = 0.002
SETUP_CHUNKS = 1   # the warm-up chunk: it compiles or loads the program and ends set-up
MODEL_SEED_MODULUS = 2 ** 31 - 1   # the program's and jax's seeds are 32-bit
DETECTOR = "gordo_tpu.anomaly.diff.DiffBasedAnomalyDetector"
PIPELINE = "gordo_tpu.pipeline.Pipeline"
SCALERS = {"MinMaxScaler": "gordo_tpu.ops.scalers.MinMaxScaler"}
ESTIMATORS = {"LSTMAutoEncoder": "gordo_tpu.models.estimator.LSTMAutoEncoder"}


# ---------------------------------------------------------------------------
# the project, from the seed
# ---------------------------------------------------------------------------

def model_seed(seed: int) -> int:
    return int(seed) % MODEL_SEED_MODULUS


def machine_names(seed: int, n: int) -> List[str]:
    return [f"s{int(seed)}-m{i:05d}" for i in range(n)]


def machine_tags(name: str, n_tags: int) -> List[str]:
    return [f"{name}-t{j}" for j in range(n_tags)]


def estimator_kwargs(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    return {**config["model"], "seed": model_seed(seed)}


def project_doc(config: Dict[str, Any], seed: int, n_machines: int) -> Dict[str, Any]:
    """The project as a plant's model team would write it: one model for
    every machine under ``globals``, one dataset entry per machine."""
    steps: List[Any] = [SCALERS[s] for s in config["scalers"]]
    steps.append({ESTIMATORS[config["estimator"]]: estimator_kwargs(config, seed)})
    if config["detector"] != "DiffBasedAnomalyDetector":
        raise ValueError(f"unknown detector {config['detector']!r}")
    model = {DETECTOR: {"base_estimator": {PIPELINE: {"steps": steps}}}}
    ds = config["dataset"]
    machines = []
    for name in machine_names(seed, n_machines):
        machines.append({
            "name": name,
            "dataset": {
                "type": ds["type"],
                "resolution": ds["resolution"],
                "train_start_date": ds["train_start_date"],
                "train_end_date": ds["train_end_date"],
                "tag_list": machine_tags(name, int(ds["n_tags"])),
            },
        })
    doc = {"globals": {"model": model}, "machines": machines}
    if "cv" in config:
        cv = config["cv"]
        if cv.get("splitter") != "TimeSeriesSplit" or int(cv.get("n_splits", 3)) != 3:
            # the detector's default is what the configuration states; another
            # splitter would have to be written into `evaluation.cv` here
            raise ValueError("only the detector's default CV is generated")
    return doc


def layout(config: Dict[str, Any]) -> Tuple[int, int]:
    """``(chunk_machines, n_chunks)``: the project in chunks.  The same
    seed is the same project whatever ``--seconds`` says."""
    deployment = config["deployment"]
    chunk = int(deployment["max_bucket_size"])
    n_chunks, rest = divmod(int(deployment["project_machines"]), chunk)
    if rest or n_chunks < SETUP_CHUNKS + 2:
        raise ValueError(
            "project_machines has to be whole chunks: the warm-up chunk, one "
            "or more to count and a last one that is never counted"
        )
    return chunk, n_chunks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class WindowError(RuntimeError):
    """The window closed before one chunk completed, or the build died."""


def _completed() -> float:
    from gordo_tpu import telemetry

    series = telemetry.REGISTRY.get(COMPLETIONS_SERIES)
    return float(series.value(COMPLETIONS_LABEL)) if series is not None else 0.0


def _snapshot() -> Dict[str, Any]:
    from gordo_tpu import telemetry

    return telemetry.REGISTRY.snapshot()["metrics"]


def run(ctx) -> Dict[str, Any]:
    """Drive one run; returns the run record the harness reduces."""
    import jax
    from gordo_tpu import compile as compile_plane
    from gordo_tpu.builder.fleet_build import build_project
    from gordo_tpu.workflow.config import NormalizedConfig

    config, traffic = ctx.config, ctx.traffic
    chunk, n_chunks = layout(config)
    setup_chunks = SETUP_CHUNKS
    countable = n_chunks - setup_chunks - 1
    names = machine_names(ctx.seed, n_chunks * chunk)
    doc = project_doc(config, ctx.seed, len(names))
    machines = NormalizedConfig(doc, f"bench-{ctx.seed}").machines
    os.makedirs(ctx.scratch, exist_ok=True)
    out_dir = tempfile.mkdtemp(prefix="build-", dir=ctx.scratch)
    ctx.log(
        f"fleet_build: {n_chunks} chunks of {chunk} machines "
        f"({setup_chunks} set-up, up to {countable} counted, the last never), "
        f"{config['dataset']['rows']} rows x {config['dataset']['n_tags']} tags"
    )

    holder: Dict[str, Any] = {}
    # the registry's counts are the process's: a run counts from where it
    # started (zero in a process of its own)
    aot_before = int(compile_plane.aot_fallbacks())
    written_before = _completed()

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
            if _completed() - written_before >= count:
                return time.time()
            if not thread.is_alive():
                return time.time() if _completed() - written_before >= count else None
            if deadline is not None and time.time() >= deadline:
                return None
            time.sleep(POLL_SECONDS)

    try:
        t0 = wait_for(setup_chunks * chunk, None)
        if t0 is None:
            raise WindowError(f"the build ended during set-up: {holder.get('error')!r}")
        trace_dir, tracer = None, None
        if ctx.trace:
            # at once: the device idles from this completion to the next
            # program's start, and that gap is what the trace is for
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
                stamp = wait_for((setup_chunks + k + 1) * chunk, t0 + ctx.seconds)
            if stamp is None:
                break
            completions.append(stamp)
            snap_end = _snapshot()
            ctx.log(f"chunk {setup_chunks + k} written {stamp - t0:.3f}s into the window")
        else:
            ctx.log("every countable chunk was written before the window closed: "
                    "the project is too small for this window")
        ctx.log(f"window closed with {len(completions)} of {countable} countable "
                "chunks written; the build runs on to its end")
        if tracer is not None:
            tracer.join()
        thread.join()
        if "error" in holder:
            raise WindowError(f"build_project raised: {holder['error']!r}")
        if not completions:
            raise WindowError(
                f"no chunk completed within {ctx.seconds}s of the window"
            )
        result = holder["result"]
        summary = result.summary()
        failed = (
            len(summary["failed"]) + int(summary["single_built"])
            + int(summary["demoted"]["machines"])
            + int(summary["aot_fallbacks"]) - aot_before
        )
        record = {
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
            "window_names": names[setup_chunks * chunk:
                                  (setup_chunks + len(completions)) * chunk],
            "work_per_chunk": _chunk_work(config, chunk),
        }
        return record
    except BaseException:
        shutil.rmtree(out_dir, ignore_errors=True)
        raise


def _trace_some(trace_dir: str, seconds: float) -> None:
    """Trace ``seconds`` of the running build from the waiting thread."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(seconds)
    jax.profiler.stop_trace()


def _chunk_work(config: Dict[str, Any], chunk: int) -> Dict[str, Any]:
    from benchmark import flops

    ds, model = config["dataset"], config["model"]
    return flops.chunk_work(
        machines=chunk,
        n_rows=int(ds["rows"]),
        n_features=int(ds["n_tags"]),
        dims=[int(d) for d in config["layer_units"]],
        lookback=int(model["lookback_window"]),
        epochs=int(model["epochs"]),
        n_splits=int(config["cv"]["n_splits"]),
    )


def cleanup(record: Dict[str, Any]) -> None:
    shutil.rmtree(record["out_dir"], ignore_errors=True)


# ---------------------------------------------------------------------------
# correctness: the window's artifacts against the plain reference
# ---------------------------------------------------------------------------

def _epoch_seconds(stamp: str) -> int:
    import datetime

    return int(datetime.datetime.fromisoformat(stamp).timestamp())


def reference_rows(config: Dict[str, Any], name: str) -> np.ndarray:
    from benchmark.reference import data

    ds = config["dataset"]
    if ds["resolution"] != "10min":
        raise ValueError("the reference's data copy is written for 10min rows")
    return data.machine_rows(
        machine_tags(name, int(ds["n_tags"])),
        _epoch_seconds(ds["train_start_date"]),
        _epoch_seconds(ds["train_end_date"]),
        resolution_s=600,
    )


def sample_names(names: Sequence[str], seed: int, n: int) -> List[str]:
    """``n`` of the window's machines, drawn from the seed; every machine
    is the same size, so there is no longest one to force in."""
    rng = np.random.default_rng(int(seed))
    picks = rng.choice(len(names), size=min(n, len(names)), replace=False)
    return [names[i] for i in sorted(int(p) for p in picks)]


def produced(out_dir: str, names: Sequence[str]) -> Dict[str, Dict[str, Any]]:
    """What the timed path wrote for ``names``: final weights, per-epoch
    loss and thresholds, read back from the packs."""
    import jax
    from gordo_tpu import artifacts

    _, refs = artifacts.discover(out_dir)
    by_name = {ref.name: ref for ref in refs}
    out = {}
    for name in names:
        detector = by_name[name].load_model()
        estimator = detector.base_estimator.steps[-1][1]
        out[name] = {
            "params": jax.tree.map(np.asarray, estimator.params_),
            "history": np.asarray(estimator.history_, dtype=np.float64),
            "thresholds": np.concatenate([
                np.atleast_1d(np.asarray(detector.aggregate_threshold_, np.float64)),
                np.asarray(detector.feature_thresholds_, np.float64).ravel(),
            ]),
        }
    return out


def compare(made: Dict[str, Any], ref: Dict[str, Any],
            log=lambda message: None) -> Dict[str, float]:
    """The numbers held against limits, for one machine.  ``made`` stands in
    the program's place (the program's artifact, or the control's fit).
    Where ``ref`` has thresholds (the reference ran the folds for this
    machine), ``threshold_gap`` is the worst threshold's."""
    import jax

    h_made = np.asarray(made["history"], np.float64)
    h_ref = np.asarray(ref["history"], np.float64)
    numbers = {
        "loss_first_gap": abs(h_made[0] - h_ref[0]) / abs(h_ref[0]),
        "loss_last_gap": abs(h_made[-1] - h_ref[-1]) / abs(h_ref[-1]),
    }
    paths = [jax.tree_util.keystr(path)
             for path, _ in jax.tree_util.tree_leaves_with_path(ref["init"])]
    init = jax.tree.leaves(ref["init"])

    def moved(params) -> List[float]:
        """Per leaf, the norm of the change from the reference's start."""
        return [
            float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
            for a, b in zip(jax.tree.leaves(params), init)
        ]

    moved_made, moved_ref = moved(made["params"]), moved(ref["params"])
    floor = float(np.median(moved_ref))
    gaps = [abs(m - r) / max(r, floor) for m, r in zip(moved_made, moved_ref)]
    at = int(np.argmax(gaps))
    numbers["update_norm_gap"] = gaps[at]
    log(f"update_norm_gap {gaps[at]:.4g} at leaf {paths[at]}: moved "
        f"{moved_made[at]:.4g}, reference {moved_ref[at]:.4g}, median leaf {floor:.4g}")
    nonfinite = int(not np.all(np.isfinite(h_made)))
    nonfinite += sum(
        int(not np.all(np.isfinite(np.asarray(leaf))))
        for leaf in jax.tree.leaves(made["params"])
    )
    if "thresholds" in made:
        t = np.asarray(made["thresholds"], np.float64)
        nonfinite += int(not (np.all(np.isfinite(t)) and np.all(t > 0)))
        if "thresholds" in ref:
            t_ref = np.asarray(ref["thresholds"], np.float64)
            t_gaps = np.abs(t - t_ref) / np.maximum(t_ref, np.median(t_ref))
            numbers["threshold_gap"] = float(np.max(t_gaps))
            log(f"threshold_gap {numbers['threshold_gap']:.4g} at threshold "
                f"{int(np.argmax(t_gaps))} (0 is the aggregate one); "
                f"aggregate {t[0]:.6g}, reference {t_ref[0]:.6g}")
    numbers["nonfinite"] = float(nonfinite)
    return numbers


def middle(per_machine: Sequence[Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """Each number over the sampled machines: the middle machine's (the
    median) is what a limit holds, the worst is printed beside it.  About
    one fit in fifty leaves its loss plateau inside its epochs, at a step
    that the last bit of rounding moves, and from there on neither the
    program nor any reference repeats the other; the middle of an odd
    dozen machines does not see such a fit, and a fault of the timed path
    or a lower precision moves every machine.  ``nonfinite`` is a count
    over all of them."""
    keys = dict.fromkeys(key for numbers in per_machine for key in numbers)
    out = {}
    for key in keys:
        values = [numbers[key] for numbers in per_machine if key in numbers]
        out[key] = {
            "value": float(np.sum(values) if key == "nonfinite" else np.median(values)),
            "worst": float(np.max(values)),
            "machines": len(values),
        }
    return out


def reference_of(config: Dict[str, Any], rows: np.ndarray, seed: int,
                 folds, quantize=None, max_steps=None) -> Dict[str, Any]:
    """The reference's final fit of a stack of machines (or of one) and the
    thresholds from its cross-validation: of every machine where ``folds``
    is True, of the first ``folds`` machines of a stack where it is a
    count."""
    from benchmark.reference import lstm_ae

    rows = np.asarray(rows)
    out = lstm_ae.fit(rows, config["model"], seed, quantize=quantize,
                      max_steps=max_steps)
    if folds:
        head = rows if folds is True or rows.ndim == 2 else rows[:int(folds)]
        out["thresholds"] = lstm_ae.cross_validate(
            head, config["model"], seed, int(config["cv"]["n_splits"]),
            quantize=quantize, max_steps=max_steps)
    return out


def machine_of(ref: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Machine ``i`` of a stack's reference."""
    import jax

    out = {
        "init": ref["init"],
        "params": jax.tree.map(lambda a: a[i], ref["params"]),
        "history": ref["history"][i],
    }
    if "thresholds" in ref and i < len(ref["thresholds"]):
        out["thresholds"] = ref["thresholds"][i]
    return out


def check(ctx, record: Dict[str, Any]) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """Compare a seeded sample of the window's machines with the reference's
    own fit of the same machines, all in one stack; for the first
    ``fold_machines`` of them the reference also runs the folds, and the
    thresholds are compared.  Runs after the build has returned and its
    arrays are freed; never counted in set-up or in the window."""
    config = ctx.config
    spec = config["check"]
    names = sample_names(record["window_names"], ctx.seed, int(spec["machines"]))
    made = produced(record["out_dir"], names)
    t0 = time.time()
    stack = np.stack([reference_rows(config, name) for name in names])
    ref = reference_of(config, stack, model_seed(ctx.seed),
                       folds=min(int(spec["fold_machines"]), len(names)))
    ctx.log(f"reference of {len(names)} machines: {time.time() - t0:.1f}s")
    per_machine = []
    for i, name in enumerate(names):
        numbers = compare(made[name], machine_of(ref, i),
                          lambda message, name=name: ctx.log(f"{name}: {message}"))
        per_machine.append(numbers)
    return judge(middle(per_machine), spec["limits"], ctx.log)


def judge(numbers: Dict[str, Dict[str, float]], limits: Dict[str, float],
          log) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """Every number beside its limit; a number with no limit fails."""
    table, ok = {}, True
    for key, entry in numbers.items():
        value, limit = entry["value"], limits.get(key)
        passed = limit is not None and np.isfinite(value) and value <= limit
        ok = ok and passed
        table[key] = {**entry, "limit": limit, "ok": bool(passed)}
        log(f"check {key}: value={value!r} limit={limit!r} "
            f"{'ok' if passed else 'FAIL'} (worst of {entry['machines']} "
            f"machines {entry['worst']!r})")
    return ok, table
