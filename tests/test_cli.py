"""CLI tests: the command table drives the same verbs as the reference
menu (worker.py:1629-2034) against a live localhost cluster."""

import asyncio
import io
import json
import sys

from dml_tpu.cli import NodeApp, main
from dml_tpu.config import ClusterSpec, StoreConfig, Timing

FAST = Timing(ping_interval=0.05, ack_timeout=0.15, cleanup_time=0.3,
              missed_acks_to_suspect=2, leader_rpc_timeout=5.0)


def test_localspec_roundtrip(capsys):
    main(["localspec", "-n", "3", "--base-port", "23001"])
    out = capsys.readouterr().out
    spec = ClusterSpec.from_json(out)
    assert len(spec.nodes) == 3
    assert spec.nodes[0].port == 23001
    assert spec.introducer is not None


def test_chaos_verb_dry_run_and_plan_replay(tmp_path, capsys):
    """`chaos run --dry-run` prints the seeded schedule and `--dump`
    writes a plan a later `--plan` invocation parses back — the
    save/diff/replay loop that makes a chaos schedule a shareable
    artifact."""
    import pytest

    from dml_tpu.cluster.chaos import ChaosPlan

    def run_ok(argv):
        with pytest.raises(SystemExit) as e:
            main(argv)
        assert e.value.code == 0
        return capsys.readouterr().out

    dump = tmp_path / "plan.json"
    out = run_ok(["chaos", "run", "--seed", "9", "--soak", "--dry-run",
                  "--dump", str(dump)])
    assert "crash @leader" in out and "seed=9" in out
    plan = ChaosPlan.from_dict(json.loads(dump.read_text()))
    assert plan.seed == 9 and any(e.kind == "heal" for e in plan.events)
    # replaying the dumped plan dry prints the identical schedule
    out2 = run_ok(["chaos", "run", "--plan", str(dump), "--dry-run"])
    assert out.split("plan written")[0] == out2
    # every adversarial family has a one-flag repro command
    for fam, signature in (("asym", "partition_asym"), ("disk", "disk_corrupt"),
                           ("dns", "dns_crash"), ("skew", "skew"),
                           ("fuzz", "fuzz")):
        out3 = run_ok(["chaos", "run", "--seed", "2",
                       "--scenario", fam, "--dry-run"])
        assert signature in out3 and f"{fam}-2" in out3
    with pytest.raises(SystemExit) as e:
        main(["chaos", "bogus-verb"])
    assert e.value.code != 0


async def test_nodeapp_commands(tmp_path, capsys):
    from dml_tpu.cluster.introducer import IntroducerService

    spec = ClusterSpec.localhost(
        2, base_port=23101, introducer_port=23100, timing=FAST,
        store=StoreConfig(root=str(tmp_path / "roots"),
                          download_dir=str(tmp_path / "dl")),
    )
    dns = IntroducerService(spec)
    await dns.start()
    apps = []
    try:
        for n in spec.nodes:
            app = NodeApp.__new__(NodeApp)
            app.spec = spec
            from dml_tpu.cluster.node import Node
            from dml_tpu.cluster.store_service import StoreService
            from dml_tpu.jobs.service import JobService
            app.node = Node(spec, n)
            app.store = StoreService(app.node, root=str(tmp_path / f"st_{n.port}"))

            async def fake_backend(model, paths):
                return (
                    {p.split("/")[-1]: [{"label": model, "score": 1.0}] for p in paths},
                    0.001,
                    None,
                )

            app.jobs = JobService(app.node, app.store, infer_backend=fake_backend)
            await app.start()
            apps.append(app)

        # convergence
        for _ in range(100):
            if all(a.node.joined and a.node.leader_unique for a in apps):
                break
            await asyncio.sleep(0.05)

        app = apps[-1]
        # membership + identity verbs
        assert await app.handle("list_mem")
        assert await app.handle("self_id")
        out = capsys.readouterr().out
        assert app.node.me.unique_name in out

        # file verbs
        src = tmp_path / "a.jpeg"
        src.write_bytes(b"\xff\xd8data")
        assert await app.handle(f"put {src} a.jpeg")
        assert await app.handle("ls-all")
        assert await app.handle("ls a.jpeg")
        assert await app.handle("store")
        dst = tmp_path / "back.jpeg"
        assert await app.handle(f"get a.jpeg {dst}")
        assert dst.read_bytes() == b"\xff\xd8data"
        out = capsys.readouterr().out
        assert "a.jpeg" in out and "ok version=1" in out

        # global-view + bulk verbs (reference CLI options 6/7/8 and
        # get-all, worker.py:1711-1722, 1939-1954)
        src2 = tmp_path / "b.jpeg"
        src2.write_bytes(b"\xff\xd8more")
        assert await app.handle(f"put {src2} b.jpeg")
        capsys.readouterr()
        assert await app.handle("files-per-node")
        out = capsys.readouterr().out
        assert "a.jpeg" in out and "b.jpeg" in out
        assert any(n.unique_name in out for n in spec.nodes)
        assert await app.handle("7")
        out = capsys.readouterr().out
        assert "a.jpeg" in out and "b.jpeg" in out
        assert await app.handle("file-count")
        assert capsys.readouterr().out.strip() == "2"
        bulk = tmp_path / "bulk"
        assert await app.handle(f"get-all *.jpeg {bulk}")
        out = capsys.readouterr().out
        assert "ok 2 files" in out
        assert (bulk / "a.jpeg").read_bytes() == b"\xff\xd8data"
        assert (bulk / "b.jpeg").read_bytes() == b"\xff\xd8more"

        # job verbs (fake backend)
        assert await app.handle("submit-job ResNet50 4")
        out = capsys.readouterr().out
        assert "DONE: 4 queries" in out
        assert await app.handle("C1")
        assert await app.handle("C5")
        assert await app.handle("breakdown")
        out = capsys.readouterr().out
        assert "decode_cache" in out and "pipeline_depth" in out

        # `profile spans` answers from the one recorder: the store
        # operations and the job's worker stages above are in it
        capsys.readouterr()
        assert await app.handle("profile spans")
        spans = json.loads(capsys.readouterr().out)
        for name in ("store_op_put", "store_op_get", "worker_fetch",
                     "worker_infer", "worker_put"):
            assert spans[name]["count"] >= 1, name
            assert spans[name]["max_s"] >= spans[name]["mean_s"] >= 0.0

        # stats + errors
        assert await app.handle("bps")
        assert await app.handle("fp-rate")
        assert await app.handle("bogus-command")
        out = capsys.readouterr().out
        assert "unknown command" in out
        assert await app.handle("get missing.file /tmp/x")
        assert "!!" in capsys.readouterr().out

        # quit returns False
        assert not await app.handle("quit")
    finally:
        for a in apps:
            await a.stop()
        await dns.stop()


async def test_nodeapp_lm_spec_serving(tmp_path, capsys):
    """The operator path for distributed LM serving: nodes boot with
    an --lm-spec (deterministic weights from the seed, identical on
    every node), prompts go in via `put`, and the standard
    submit-job/get-output verbs drive the LM job end-to-end."""
    from dml_tpu.cluster.introducer import IntroducerService
    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService
    from dml_tpu.inference.lm_backend import write_prompt_file
    from dml_tpu.jobs.service import JobService

    lm_spec = {
        "name": "CliLM", "vocab_size": 61, "d_model": 32,
        "n_heads": 4, "n_kv_heads": 2, "n_layers": 2, "d_ff": 64,
        "dtype": "float32", "max_new_tokens": 6, "max_slots": 2,
        "max_len": 64, "chunk": 4, "seed": 3,
    }
    spec = ClusterSpec.localhost(
        2, base_port=23151, introducer_port=23150, timing=FAST,
        store=StoreConfig(root=str(tmp_path / "roots"),
                          download_dir=str(tmp_path / "dl")),
    )
    dns = IntroducerService(spec)
    await dns.start()
    apps = []
    try:
        for n in spec.nodes:
            app = NodeApp.__new__(NodeApp)
            app.spec = spec
            app.node = Node(spec, n)
            app.store = StoreService(app.node, root=str(tmp_path / f"st_{n.port}"))
            app.jobs = JobService(app.node, app.store)
            app._lm_specs = [dict(lm_spec)]
            await app.start()
            apps.append(app)
        for _ in range(100):
            if all(a.node.joined and a.node.leader_unique for a in apps):
                break
            await asyncio.sleep(0.05)

        out = capsys.readouterr().out
        assert "registered LM serving model 'CliLM'" in out

        app = apps[-1]
        p = tmp_path / "p0.tokens.txt"
        write_prompt_file(str(p), [3, 1, 4, 1, 5])
        assert await app.handle(f"put {p} p0.tokens.txt")
        # case-insensitive model resolution through the CLI verb
        assert await app.handle("submit-job clilm 3")
        out = capsys.readouterr().out
        assert "DONE: 3 queries" in out
        assert await app.handle("get-output 1")
        out = capsys.readouterr().out
        assert "ok 1 results" in out
        # the merged output file holds the completion tokens
        import json as _json

        with open("final_1.json") as f:
            merged = _json.load(f)
        assert list(merged) == ["p0.tokens.txt"]
        assert len(merged["p0.tokens.txt"]["tokens"]) == 6
    finally:
        import contextlib
        import os as _os

        with contextlib.suppress(FileNotFoundError):
            _os.unlink("final_1.json")
        for app in reversed(apps):
            await app.stop()
        await dns.stop()


# ----------------------------------------------------------------------
# log-path hygiene (ISSUE 8 satellite: debug.log must never reappear)
# ----------------------------------------------------------------------


def test_default_log_path_never_working_directory(monkeypatch, tmp_path):
    """`debug.log` materialized in the repo root twice (PR 7 removed
    it, it came back) because `_setup_logging` defaulted to a RELATIVE
    path — whatever directory a test/bench/operator shell happened to
    start the process from. The default must be absolute, live under
    the system tempdir in a PRIVATE owner-verified dir (no
    predictable world-writable /tmp filename another user could
    pre-plant, CWE-377), and carry a per-process name so concurrent
    nodes don't interleave one file. `DML_TPU_LOG_FILE` is the
    explicit override."""
    import os
    import stat
    import tempfile

    from dml_tpu.cli import default_log_path

    monkeypatch.delenv("DML_TPU_LOG_FILE", raising=False)
    p = default_log_path()
    assert os.path.isabs(p)
    assert os.path.commonpath([p, tempfile.gettempdir()]) == \
        tempfile.gettempdir()
    assert os.path.dirname(p) != os.getcwd()
    assert os.path.basename(p) != "debug.log"
    assert f"_{os.getpid()}" in os.path.basename(p)
    d = os.path.dirname(p)
    st = os.lstat(d)
    assert stat.S_ISDIR(st.st_mode)
    if hasattr(os, "geteuid"):
        assert st.st_uid == os.geteuid()
        assert stat.S_IMODE(st.st_mode) == 0o700
    # explicit override wins, ~ expanded
    override = tmp_path / "node.log"
    monkeypatch.setenv("DML_TPU_LOG_FILE", str(override))
    assert default_log_path() == str(override)


async def test_cluster_sim_leaves_no_repo_root_artifacts(tmp_path):
    """A DEFAULT cluster sim run (the chaos.LocalCluster bring-up
    every chaos/bench/ingress path shares) must not litter the repo
    root: no debug.log, no stray merged-output files, nothing. The
    sweep is exhaustive over new entries rather than a denylist so the
    NEXT litter bug fails here too."""
    import os

    import dml_tpu
    from dml_tpu.cluster import chaos

    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(dml_tpu.__file__)))
    # pytest/tooling churn that is not product output
    infra = {".pytest_cache", "__pycache__", ".hypothesis"}
    before = set(os.listdir(repo_root)) | infra
    c = chaos.LocalCluster(3, str(tmp_path / "sim"), 23980, seed=0)
    try:
        await c.start()
        await c.wait_for(c.converged, 15.0, "initial convergence")
        client = c.client()
        await client.store.put_bytes(
            "artifact_probe.jpeg", b"x" * 256, timeout=20.0)
    finally:
        await c.stop()
    new = set(os.listdir(repo_root)) - before
    assert not new, f"cluster sim littered the repo root: {sorted(new)}"
