"""Workflow-generator tests — assert on the generated orchestration
documents, never a live cluster (reference test pattern, SURVEY.md §5)."""

import yaml

from gordo_tpu.workflow import (
    NormalizedConfig,
    build_plan,
    generate_workflow,
    unique_tags,
    workflow_to_yaml,
)

PROJECT = {
    "machines": [
        {"name": "gen-a", "dataset": {
            "type": "RandomDataset", "tags": ["t1", "t2"],
            "train_start_date": "2017-01-01T00:00:00Z",
            "train_end_date": "2017-01-02T00:00:00Z"}},
        {"name": "gen-b", "dataset": {
            "type": "RandomDataset", "tags": ["t2", "t3"],
            "train_start_date": "2017-01-01T00:00:00Z",
            "train_end_date": "2017-01-02T00:00:00Z"}},
        {"name": "gen-c", "dataset": {
            "type": "RandomDataset", "tags": ["t4", "t5", "t6"],
            "train_start_date": "2017-01-01T00:00:00Z",
            "train_end_date": "2017-01-02T00:00:00Z"}},
    ],
}


def _config():
    return NormalizedConfig(PROJECT, "genproj")


def test_unique_tags():
    assert unique_tags(_config().machines) == ["t1", "t2", "t3", "t4", "t5", "t6"]


def test_build_plan_buckets_by_signature():
    plan = build_plan(_config())
    assert plan["project-name"] == "genproj"
    assert plan["n_machines"] == 3
    # same default model: 2-tag machines bucket together, 3-tag separately
    assert plan["n_buckets"] == 2
    sizes = sorted(b["n_machines"] for b in plan["buckets"])
    assert sizes == [1, 2]
    two_tag = next(b for b in plan["buckets"] if b["n_machines"] == 2)
    assert sorted(two_tag["machines"]) == ["gen-a", "gen-b"]
    assert set(two_tag["cache_keys"]) == {"gen-a", "gen-b"}


def test_build_plan_reports_fetch_dedup_projection():
    """r24: `workflow plan` surfaces the ingest plane's fetch dedup —
    the operator sees the provider-fetch bill before building."""
    import copy

    project = copy.deepcopy(PROJECT)
    # twin of gen-a: identical dataset config, distinct name
    project["machines"].append(
        {"name": "gen-a-twin",
         "dataset": dict(project["machines"][0]["dataset"])}
    )
    plan = build_plan(NormalizedConfig(project, "genproj"))
    assert plan["ingest"] == {
        "distinct_dataset_fingerprints": 3,
        "dedup_hits": 1,
        "fetch_dedup_ratio": 0.25,
    }
    # no twins → no projected dedup
    assert build_plan(_config())["ingest"]["dedup_hits"] == 0


def test_build_plan_respects_max_bucket_size():
    plan = build_plan(_config(), max_bucket_size=1)
    assert plan["n_buckets"] == 3
    assert all(b["n_machines"] == 1 for b in plan["buckets"])


def _ragged_project(n_filtered=3, n_plain=2):
    """A bucket whose configs predict multiple distinct train lengths:
    row-filtered machines (each an unpredictable length) riding with
    uniform-window plain ones."""
    return {
        "machines": [
            {"name": f"rg-f-{i}", "dataset": {
                "type": "RandomDataset", "tags": ["t1", "t2"],
                "train_start_date": "2017-01-01T00:00:00Z",
                "train_end_date": "2017-01-02T00:00:00Z",
                "row_filter": f"`t1` > 0.{i}"}}
            for i in range(n_filtered)
        ] + [
            {"name": f"rg-p-{i}", "dataset": {
                "type": "RandomDataset", "tags": ["t1", "t2"],
                "train_start_date": "2017-01-01T00:00:00Z",
                "train_end_date": "2017-01-02T00:00:00Z"}}
            for i in range(n_plain)
        ],
    }


def test_build_plan_warns_on_predicted_ragged_compiles():
    """Neither align_lengths nor pad_lengths + length-diverse configs →
    the plan must carry the estimated compile bill (ADVICE r5 item 5,
    warning-only slice: explicit, not silent)."""
    plan = build_plan(NormalizedConfig(_ragged_project(), "rgproj"))
    warning = plan["ragged_compile_warning"]
    # 3 row-filtered (one predicted length each) + 1 shared plain window
    # = 4 predicted lengths in 1 bucket → 3 compiles beyond the floor
    assert warning["estimated_distinct_lengths"] == 4
    assert warning["estimated_extra_compiles"] == 3
    assert warning["estimated_extra_compile_seconds"] > 0
    assert "align_lengths" in warning["hint"]


def test_build_plan_warning_silenced_by_length_strategy():
    cfg = NormalizedConfig(_ragged_project(), "rgproj")
    aligned = build_plan(cfg, align_lengths=256)
    assert "ragged_compile_warning" not in aligned
    assert aligned["align_lengths"] == 256
    padded = build_plan(cfg, pad_lengths=128)
    assert "ragged_compile_warning" not in padded
    assert padded["pad_lengths"] == 128
    # pad_lengths is part of the planned cache identity: keys must differ
    # from an exact-mode plan's (they'd never match the registry entries
    # a padded build writes)
    exact = build_plan(cfg)
    bucket_p = padded["buckets"][0]["cache_keys"]
    bucket_e = exact["buckets"][0]["cache_keys"]
    assert all(bucket_p[m] != bucket_e[m] for m in bucket_p)


def test_build_plan_uniform_project_has_no_warning():
    plan = build_plan(_config())
    assert "ragged_compile_warning" not in plan


def test_generate_workflow_documents():
    docs = generate_workflow(_config())
    kinds = [d["kind"] for d in docs]
    assert kinds.count("Job") == 1              # ONE builder job, not 3 pods
    assert kinds.count("Deployment") == 2       # ml-server + watchman
    assert kinds.count("Service") == 2
    assert kinds.count("Mapping") == 4          # per-machine + stream routes
    assert kinds.count("ConfigMap") == 1        # embedded build plan

    job = next(d for d in docs if d["kind"] == "Job")
    container = job["spec"]["template"]["spec"]["containers"][0]
    assert container["command"] == ["gordo", "build-project"]
    assert "google.com/tpu" in container["resources"]["limits"]

    mappings = [d for d in docs if d["kind"] == "Mapping"]
    prefixes = {m["spec"]["prefix"] for m in mappings}
    assert "/gordo/v0/genproj/gen-a/" in prefixes

    plan_cm = next(d for d in docs if d["kind"] == "ConfigMap")
    embedded = yaml.safe_load(plan_cm["data"]["plan.yaml"])
    assert embedded["n_machines"] == 3


def test_generate_workflow_stream_route_is_sse_safe():
    """The streaming plane rides long-lived SSE connections: its Mapping
    must disable Ambassador's request timeout and stretch the idle
    timeout past the keepalive cadence, and the Services in front of the
    server/watchman must carry the LB connection-idle annotation."""
    docs = generate_workflow(_config())
    stream = next(
        d for d in docs
        if d["kind"] == "Mapping" and "stream" in d["metadata"]["name"]
    )
    assert stream["spec"]["prefix"] == "/gordo/v0/genproj/stream"
    assert stream["spec"]["timeout_ms"] == 0
    assert stream["spec"]["idle_timeout_ms"] == 86_400_000
    assert stream["spec"]["service"].startswith("gordo-ml-server")

    # per-machine mappings keep their request timeouts — only the
    # stream route is exempt
    for m in (d for d in docs if d["kind"] == "Mapping"):
        if m is not stream:
            assert "timeout_ms" not in m["spec"]

    for svc in (d for d in docs if d["kind"] == "Service"):
        annotations = svc["metadata"]["annotations"]
        key = (
            "service.beta.kubernetes.io/"
            "aws-load-balancer-connection-idle-timeout"
        )
        assert annotations[key] == "3600"


def test_generate_argo_workflow_dag_per_chunk():
    """The Argo shim: one Workflow doc, a DAG task per fleet chunk, each
    parameterized with its chunk's machine list and running the
    --machines-filtered build-project."""
    from gordo_tpu.workflow.generator import generate_argo_workflow

    wf = generate_argo_workflow(_config(), image="img:1", max_bucket_size=1)
    assert wf["apiVersion"] == "argoproj.io/v1alpha1"
    assert wf["kind"] == "Workflow"
    templates = {t["name"]: t for t in wf["spec"]["templates"]}
    tasks = templates["build"]["dag"]["tasks"]
    assert len(tasks) == 3  # max_bucket_size=1 -> one chunk per machine
    machine_params = sorted(
        t["arguments"]["parameters"][0]["value"] for t in tasks
    )
    assert machine_params == ["gen-a", "gen-b", "gen-c"]
    container = templates["build-chunk"]["container"]
    assert container["image"] == "img:1"
    assert container["command"] == ["gordo", "build-project"]
    assert "--machines" in container["args"]
    # chunk tasks are independent — Argo parallelizes them
    assert all("dependencies" not in t for t in tasks)

    # multi-machine chunks carry comma-joined names
    wf2 = generate_argo_workflow(_config(), max_bucket_size=512)
    tasks2 = {
        t["arguments"]["parameters"][0]["value"]
        for t in wf2["spec"]["templates"][0]["dag"]["tasks"]
    }
    assert "gen-a,gen-b" in tasks2


def test_workflow_yaml_roundtrip():
    docs = generate_workflow(_config())
    parsed = list(yaml.safe_load_all(workflow_to_yaml(docs)))
    assert len(parsed) == len(docs)
    assert parsed[0]["kind"] == "Job"


def test_server_deployment_args_and_warmup_default():
    """The ml-server Deployment warms up by default (pods must not serve
    cold-compile responses after a reschedule) and carries user-supplied
    extra run-server flags."""
    docs = generate_workflow(
        _config(), server_args=["--coalesce-ms", "2", "--model-parallel"]
    )
    dep = next(
        d for d in docs
        if d["kind"] == "Deployment"
        and d["metadata"]["name"].startswith("gordo-server-")
    )
    args = dep["spec"]["template"]["spec"]["containers"][0]["args"]
    assert "--warmup" in args
    i = args.index("--coalesce-ms")
    assert args[i: i + 3] == ["--coalesce-ms", "2", "--model-parallel"]


def test_generate_workflow_multihost_indexed_job():
    """--multihost N: the builder becomes an N-pod Indexed Job wired with
    the GORDO_* env contract and a headless Service giving pod 0 a stable
    coordinator DNS name."""
    docs = generate_workflow(_config(), multihost=2)
    job = next(d for d in docs if d["kind"] == "Job")
    assert job["spec"]["completionMode"] == "Indexed"
    assert job["spec"]["completions"] == 2
    assert job["spec"]["parallelism"] == 2
    pod = job["spec"]["template"]["spec"]
    assert pod["subdomain"] == "gordo-builder-genproj"
    env = {
        e["name"]: e["value"]
        for e in pod["containers"][0]["env"]
    }
    assert env["GORDO_NUM_PROCESSES"] == "2"
    assert env["GORDO_PROCESS_ID"] == "$(JOB_COMPLETION_INDEX)"
    assert env["GORDO_COORDINATOR"].startswith("gordo-builder-genproj-0.")
    # the headless service exists and has no cluster VIP
    headless = next(
        d for d in docs
        if d["kind"] == "Service"
        and d["metadata"]["name"] == "gordo-builder-genproj"
    )
    assert headless["spec"]["clusterIP"] == "None"


def test_generate_workflow_multihost_one_process_is_plain_job():
    docs = generate_workflow(_config(), multihost=1)
    job = next(d for d in docs if d["kind"] == "Job")
    assert "completionMode" not in job["spec"]


def test_generate_workflow_refuses_oversharded_multihost():
    """Bugfix (ISSUE 2 satellite): N beyond the machine-shard count is a
    config error with a clear message, not a manifest with idle
    barrier-holding pods."""
    import pytest

    with pytest.raises(ValueError, match="machine-shard count"):
        generate_workflow(_config(), multihost=4)  # only 3 machines
    with pytest.raises(ValueError, match="multihost"):
        generate_workflow(_config(), multihost=0)


def test_scrape_annotations_on_by_default():
    """Server and watchman pod templates carry the prometheus.io/*
    discovery annotations (their /metrics endpoints are the scrape
    surfaces) pointing at each component's own port."""
    docs = generate_workflow(_config())
    deployments = {
        d["metadata"]["name"]: d for d in docs if d["kind"] == "Deployment"
    }
    server_meta = deployments["gordo-server-genproj"]["spec"]["template"][
        "metadata"
    ]
    watchman_meta = deployments["gordo-watchman-genproj"]["spec"][
        "template"
    ]["metadata"]
    for meta, port in ((server_meta, "5555"), (watchman_meta, "5556")):
        ann = meta["annotations"]
        assert ann["prometheus.io/scrape"] == "true"
        assert ann["prometheus.io/port"] == port
        assert ann["prometheus.io/path"] == "/metrics"


def test_scrape_annotations_opt_out():
    docs = generate_workflow(_config(), scrape_annotations=False)
    for doc in docs:
        if doc["kind"] == "Deployment":
            meta = doc["spec"]["template"]["metadata"]
            assert "annotations" not in meta


def test_compile_cache_volume_on_builder_and_server():
    """Builder Job and server Deployment share one per-project compile
    cache: JAX_COMPILATION_CACHE_DIR points both at the same mounted PVC,
    so a rescheduled server loads executables the builder (or a previous
    server) already compiled (ISSUE 5 satellite)."""
    docs = generate_workflow(_config())
    job = next(d for d in docs if d["kind"] == "Job")
    dep = next(
        d for d in docs
        if d["kind"] == "Deployment"
        and d["metadata"]["name"].startswith("gordo-server-")
    )
    for doc in (job, dep):
        pod = doc["spec"]["template"]["spec"]
        container = pod["containers"][0]
        env = {e["name"]: e["value"] for e in container["env"]}
        assert env["JAX_COMPILATION_CACHE_DIR"] == "/compile-cache"
        mounts = {m["name"]: m for m in container["volumeMounts"]}
        assert mounts["compile-cache"]["mountPath"] == "/compile-cache"
        assert not mounts["compile-cache"].get("readOnly")
        volumes = {v["name"]: v for v in pod["volumes"]}
        assert volumes["compile-cache"]["persistentVolumeClaim"][
            "claimName"
        ] == "gordo-compile-cache-genproj"


def test_multihost_workers_share_the_compile_cache_path():
    """Every worker of a --multihost Indexed Job extends the builder
    template, so all N processes point at the SAME cache path and each
    fleet program compiles once per fleet, not once per process."""
    docs = generate_workflow(_config(), multihost=2)
    job = next(d for d in docs if d["kind"] == "Job")
    env = {
        e["name"]: e["value"]
        for e in job["spec"]["template"]["spec"]["containers"][0]["env"]
    }
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/compile-cache"
    assert env["GORDO_NUM_PROCESSES"] == "2"
