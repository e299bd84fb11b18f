"""Artifact store + deploy CLI + generic pool."""

import asyncio
import io
import json
import tarfile
import threading

import pytest

from dynamo_tpu.components.artifact_store import ArtifactStore, build_app, serve
from dynamo_tpu.runtime.pool import Pool


def _bundle_tar(manifest: dict) -> bytes:
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tf:
        data = json.dumps(manifest).encode()
        info = tarfile.TarInfo("bundle/manifest.json")
        info.size = len(data)
        tf.addfile(info, io.BytesIO(data))
    return buf.getvalue()


def test_artifact_store_roundtrip(tmp_path):
    store = ArtifactStore(str(tmp_path))
    blob = _bundle_tar({"kind": "dynamo_tpu_bundle", "graph": "g:G"})
    meta = store.put_artifact("demo", blob)
    assert meta["manifest"]["graph"] == "g:G"
    assert store.list_artifacts()[0]["digest"] == meta["digest"]
    assert store.get_artifact(meta["digest"]) is not None

    dep = store.put_deployment("prod", meta["digest"], {"replicas": 2})
    assert store.get_deployment("prod")["config"]["replicas"] == 2
    assert store.delete_deployment("prod")
    assert store.get_deployment("prod") is None
    assert store.delete_artifact(meta["digest"])
    assert store.get_artifact(meta["digest"]) is None


def test_artifact_store_http_and_deploy_cli(tmp_path, run, capsys):
    """End to end over HTTP: serve the store, push a bundle through the
    `dynamo deploy` CLI command, create + fetch the deployment."""
    blob = _bundle_tar({"kind": "dynamo_tpu_bundle", "graph": "g:G"})
    bundle_path = tmp_path / "demo_bundle.tar.gz"
    bundle_path.write_bytes(blob)

    async def go():
        runner = await serve(str(tmp_path / "root"), "127.0.0.1", 0)
        port = runner.addresses[0][1]

        import argparse

        from dynamo_tpu.sdk.cli import deploy_cmd

        args = argparse.Namespace(
            bundle=str(bundle_path), store=f"http://127.0.0.1:{port}",
            name=None, create=True, config_file=None,
        )
        await asyncio.to_thread(deploy_cmd, args)

        import aiohttp

        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/v1/deployments") as r:
                deps = (await r.json())["deployments"]
            assert deps and deps[0]["name"] == "demo_bundle"
            async with s.get(
                f"http://127.0.0.1:{port}/v1/artifacts/{deps[0]['artifact']}"
            ) as r:
                assert await r.read() == blob
        await runner.cleanup()

    run(go())
    out = capsys.readouterr().out
    assert "pushed demo_bundle" in out


def test_pool_raii_and_sharing():
    created = []
    pool = Pool(lambda: created.append(1) or object(), max_size=2)
    a = pool.acquire()
    b = pool.acquire()
    assert pool.live_count == 2
    with pytest.raises(TimeoutError):
        pool.acquire(timeout=0.05)
    a.release()
    c = pool.acquire(timeout=1.0)  # reuses a's value
    assert len(created) == 2
    assert c.value is a.value
    b.release()

    # context-manager release
    with c:
        pass
    assert pool.free_count == 2

    # shared handle returns only on last release
    s = pool.acquire_shared()
    s2 = s.share()
    s.release()
    assert pool.free_count == 1  # still held by s2
    s2.release()
    assert pool.free_count == 2

    # blocked acquire wakes when another thread releases
    x = pool.acquire()
    y = pool.acquire()
    got = []

    def waiter():
        item = pool.acquire(timeout=5.0)
        got.append(item)

    t = threading.Thread(target=waiter)
    t.start()
    x.release()
    t.join(timeout=5.0)
    assert got and got[0].value is x.value
    y.release()
    got[0].release()


def test_pool_reset_failure_drops_value():
    calls = []

    def bad_reset(v):
        calls.append(v)
        raise RuntimeError("cannot reset")

    pool = Pool(lambda: object(), max_size=1, reset=bad_reset)
    item = pool.acquire()
    item.release()
    assert calls  # reset ran
    assert pool.free_count == 0 and pool.live_count == 0
    pool.acquire(timeout=1.0)  # slot was freed: a new value can be created


def test_llmctl_disagg_get_set_roundtrip(run, capsys):
    """`llmctl disagg set` writes the watched config key; a live policy
    picks the new thresholds up without restart (disagg/router.py)."""
    import asyncio
    import json as _json

    from dynamo_tpu.cli.llmctl import amain
    from dynamo_tpu.disagg.protocols import CONFIG_KEY, DisaggConfig
    from dynamo_tpu.disagg.router import DisaggPolicy, watch_disagg_config
    from dynamo_tpu.runtime.statestore import StateStoreClient, StateStoreServer

    async def go():
        ss = StateStoreServer(port=0)
        await ss.start()
        try:
            policy = DisaggPolicy(
                "e1", DisaggConfig(), enqueue=lambda r: None, queue_len=lambda: 0
            )
            store = await StateStoreClient.connect(ss.url)
            watcher = asyncio.create_task(
                watch_disagg_config(store, "dz", policy)
            )
            await asyncio.sleep(0.1)

            rc = await amain([
                "--statestore", ss.url, "--namespace", "dz",
                "disagg", "set", "--max-local-prefill-length", "2222",
            ])
            assert rc == 0
            for _ in range(50):
                if policy.config.max_local_prefill_length == 2222:
                    break
                await asyncio.sleep(0.05)
            assert policy.config.max_local_prefill_length == 2222

            rc = await amain(["--statestore", ss.url, "--namespace", "dz",
                              "disagg", "get"])
            assert rc == 0
            watcher.cancel()
            await store.close()
        finally:
            await ss.stop()

    run(go())
    out = capsys.readouterr().out
    assert '"max_local_prefill_length": 2222' in out
