"""Operator tool tail: genesisgen, gen-p2p-identity, activeset,
poet certifier (VERDICT r2 item 10; reference cmd/genesisgen,
cmd/gen-p2p-identity, cmd/activeset, activation/certifier.go:246)."""

import asyncio
import hashlib
import io
import json
from contextlib import redirect_stdout

import pytest

from spacemesh_tpu.core.signing import EdSigner, EdVerifier
from spacemesh_tpu.node.config import GenesisConfig
from spacemesh_tpu.tools import activeset, gen_p2p_identity, genesisgen


def _run(tool_main, argv) -> list[dict]:
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = tool_main(argv)
    assert rc == 0, buf.getvalue()
    return [json.loads(line) for line in buf.getvalue().splitlines()]


def test_genesisgen_roundtrip():
    lines = _run(genesisgen.main,
                 ["--time", "2026-01-01T00:00:00Z", "--extra", "t-net",
                  "-n", "3"])
    head, keys = lines[0], lines[1:]
    assert len(keys) == 3
    # genesis id matches the config the node would derive
    import datetime

    ts = datetime.datetime.fromisoformat(
        "2026-01-01T00:00:00+00:00").timestamp()
    assert head["genesis_id"] == \
        GenesisConfig(time=ts, extra_data="t-net").genesis_id.hex()
    # each key reloads into a signer with the advertised id
    prefix = bytes.fromhex(head["genesis_id"])
    for k in keys:
        s = EdSigner(seed=bytes.fromhex(k["private"]), prefix=prefix)
        assert s.node_id.hex() == k["id"]
        assert len(bytes.fromhex(k["commitment"])) == 32


def test_genesisgen_rejects_bad_time():
    assert genesisgen.main(["--time", "not-a-time"]) == 1


def test_gen_p2p_identity_writes_node_key(tmp_path):
    (out,) = _run(gen_p2p_identity.main, ["--data-dir", str(tmp_path)])
    key_file = tmp_path / "identities" / "local.key"
    assert key_file.exists()
    prefix = GenesisConfig(time=0.0, extra_data="tpu-mainnet").genesis_id
    s = EdSigner(seed=bytes.fromhex(key_file.read_text().strip()),
                 prefix=prefix)
    assert s.node_id.hex() == out["node_id"]
    # the node picks it up as its primary identity
    from spacemesh_tpu.node.app import App
    from spacemesh_tpu.node.config import load

    cfg = load("standalone", overrides={"data_dir": str(tmp_path),
                                        "genesis": {"time": 0.0}})
    cfg.genesis.extra_data = "tpu-mainnet"
    app = App(cfg)
    try:
        assert app.signer.node_id.hex() == out["node_id"]
    finally:
        app.close()
    # refuses to clobber
    assert gen_p2p_identity.main(["--data-dir", str(tmp_path)]) == 1


def test_activeset_reads_epoch_atxs(tmp_path):
    from spacemesh_tpu.storage import db as dbmod

    # reuse a populated state db from a quick standalone prepare run?
    # cheaper: store two hand-built ATXs directly
    from spacemesh_tpu.core.types import (
        ActivationTx,
        MerkleProof,
        NIPost,
        Post,
        PostMetadataWire,
    )
    from spacemesh_tpu.storage import atxs as atxstore

    db = dbmod.open_state(tmp_path / "state.db")
    prefix = b"\x01" * 20
    nipost = NIPost(
        membership=MerkleProof(leaf_index=0, nodes=[]),
        post=Post(nonce=0, indices=[1, 2], pow_nonce=0),
        post_metadata=PostMetadataWire(challenge=bytes(32),
                                       labels_per_unit=256))
    for i in range(2):
        s = EdSigner(prefix=prefix)
        atx = ActivationTx(
            publish_epoch=3, prev_atx=bytes(32), pos_atx=bytes(32),
            commitment_atx=None, initial_post=None, nipost=nipost,
            num_units=2 + i, vrf_nonce=0,
            vrf_public_key=s.node_id, coinbase=bytes(24),
            node_id=s.node_id, signature=bytes(64))
        atxstore.add(db, atx, tick_height=10)

    (out,) = _run(activeset.main, ["3", str(tmp_path / "state.db")])
    assert out["epoch"] == 3
    assert out["count"] == 2
    assert out["total_weight"] == (2 * 10) + (3 * 10)
    db.close()


def test_node_obtains_poet_cert_from_configured_certifier(tmp_path):
    """poet_certifier config -> the node proves + certifies each identity
    at smeshing start and carries the cert into poet registration."""
    from spacemesh_tpu.consensus.certifier import (
        CertifierDaemon,
        CertifierService,
        verify_cert,
    )
    from spacemesh_tpu.node.app import App
    from spacemesh_tpu.node.config import load
    from spacemesh_tpu.post.prover import ProofParams

    params = ProofParams(k1=64, k2=8, k3=4,
                         pow_difficulty=b"\x20" + b"\xff" * 31)
    certifier_signer = EdSigner()
    service = CertifierService(certifier_signer, params, scrypt_n=2)

    async def go():
        daemon = CertifierDaemon(service)
        host, port = await daemon.start()
        cfg = load("standalone", overrides={
            "data_dir": str(tmp_path / "node"),
            "poet_certifier": f"{host}:{port}",
            "post": {"labels_per_unit": 256, "scrypt_n": 2, "k1": 64,
                     "k2": 8, "k3": 4, "min_num_units": 1,
                     "pow_difficulty": "20" + "ff" * 31},
            "smeshing": {"start": True, "num_units": 1,
                         "init_batch": 128},
        })
        app = App(cfg)
        try:
            await asyncio.wait_for(app.prepare(), 300)
            for b in app.atx_builders:
                cert = b.poet_cert
                assert cert is not None, "builder never certified"
                assert cert.node_id == b.signer.node_id
                assert verify_cert(cert, certifier_signer.public_key,
                                   EdVerifier())
        finally:
            app.close()
            await daemon.stop()

    asyncio.run(go())


def test_certifier_flow_gates_poet_registration(tmp_path):
    """POST proof -> certifier cert -> cert-gated poet accepts; no cert
    or forged cert -> rejected (activation/certifier.go:246 +
    cert-checking poet)."""
    from spacemesh_tpu.consensus.certifier import (
        CertifierClient,
        CertifierDaemon,
        CertifierService,
        PoetCert,
    )
    from spacemesh_tpu.consensus.poet import PoetService
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.prover import ProofParams, Prover

    node_id = hashlib.sha256(b"cert-node").digest()
    commitment = hashlib.sha256(b"cert-commitment").digest()
    params = ProofParams(k1=64, k2=8, k3=4,
                         pow_difficulty=b"\x20" + b"\xff" * 31)
    d = tmp_path / "post"
    initializer.initialize(d, node_id=node_id, commitment=commitment,
                           num_units=1, labels_per_unit=256, scrypt_n=2,
                           batch_size=128)
    challenge = hashlib.sha256(b"cert-challenge").digest()
    proof = Prover(d, params, batch_labels=256).prove(challenge)

    certifier_signer = EdSigner()
    service = CertifierService(certifier_signer, params, scrypt_n=2)

    async def go():
        daemon = CertifierDaemon(service)
        addr = await daemon.start()
        try:
            client = CertifierClient(addr)
            # blocking socket calls go off-loop (the daemon runs here)
            assert await asyncio.to_thread(client.pubkey) == \
                certifier_signer.public_key
            cert = await asyncio.to_thread(
                client.certificate, proof=proof, challenge=challenge,
                node_id=node_id, commitment=commitment, num_units=1,
                labels_per_unit=256)
            # caching: second call hits the cache (same object)
            again = client.certificate(
                proof=proof, challenge=challenge, node_id=node_id,
                commitment=commitment, num_units=1, labels_per_unit=256)
            assert again is cert

            # the registering identity must HOLD the certified key:
            # registration is bound by a POET-domain signature.  The POST
            # data's node_id in this test is a hash, not an ed25519 key,
            # so mint a cert for a real signer's id directly (the signing
            # path is what's under test here, not the proof re-check).
            from spacemesh_tpu.core.signing import Domain

            id_signer = EdSigner()
            cert2 = PoetCert(node_id=id_signer.node_id, expiry=0.0,
                             signature=b"")
            cert2.signature = certifier_signer.sign(
                Domain.POET_CERT, cert2.signed_bytes())
            poet = PoetService(poet_id=b"p" * 32, ticks=4,
                               certifier_pubkey=certifier_signer.public_key,
                               verifier=EdVerifier())
            sig = id_signer.sign(Domain.POET, b"r1" + challenge)
            await poet.register("r1", challenge,
                                node_id=id_signer.node_id,
                                signature=sig, cert=cert2)
            with pytest.raises(PermissionError):
                await poet.register("r1", challenge)  # nothing presented
            with pytest.raises(PermissionError):  # cert/identity mismatch
                await poet.register("r1", challenge, node_id=node_id,
                                    signature=sig, cert=cert2)
            forged = PoetCert(node_id=id_signer.node_id, expiry=0.0,
                              signature=b"\x00" * 64)
            with pytest.raises(PermissionError):
                await poet.register("r1", challenge,
                                    node_id=id_signer.node_id,
                                    signature=sig, cert=forged)
            with pytest.raises(PermissionError):  # wrong reg signature
                await poet.register("r2", challenge,
                                    node_id=id_signer.node_id,
                                    signature=sig, cert=cert2)

            # a proof that does not verify is refused by the certifier
            bad = hashlib.sha256(b"other").digest()
            with pytest.raises(RuntimeError, match="verification|failed"):
                await asyncio.to_thread(
                    client.certificate, proof=proof, challenge=challenge,
                    node_id=bad, commitment=commitment, num_units=1,
                    labels_per_unit=256)
        finally:
            await daemon.stop()

    asyncio.run(go())


def test_profiler_lists_providers_and_recommends(capsys):
    """Operator tuning tool (reference post_supervisor.go:105-127
    Providers()/Benchmark(); post-rs profiler binary): providers
    enumerate, a tiny benchmark produces per-provider rates and a
    recommendation with an init-batch suggestion for device providers."""
    import json as _json

    from spacemesh_tpu.tools import profiler

    assert profiler.main(["--providers"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    ids = [p["id"] for p in doc["providers"]]
    assert "cpu:openssl" in ids
    assert any(i.startswith("jax:") for i in ids)

    assert profiler.main(["--n", "2", "--batches", "32", "--reps", "1",
                          "--cpu-labels", "4"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    assert doc["scrypt_n"] == 2
    rec = doc["recommendation"]
    assert rec["labels_per_sec"] > 0
    assert "hours_per_space_unit" in rec
    rates = [p["labels_per_sec"] for p in doc["providers"]]
    assert rates == sorted(rates, reverse=True)
    jax_row = next(p for p in doc["providers"]
                   if p["id"].startswith("jax:"))
    assert jax_row["best_batch"] == 32


def test_profiler_verify_benchmark(capsys):
    """--verify measures proofs/second through the batched verifier
    (BASELINE config 3's metric) on a real tiny unit + proof."""
    import json as _json

    from spacemesh_tpu.tools import profiler

    assert profiler.main(["--verify", "--verify-batches", "10,20"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    rates = doc["verify"]
    assert [r["batch"] for r in rates] == [10, 20]
    assert all(r["proofs_per_sec"] > 0 for r in rates)


def test_profiler_pipeline_stage_timings(capsys):
    """--pipeline dumps per-stage (dispatch/fetch/write/stall) host
    seconds of a real streaming init, so a stalled stage is visible
    without a full profile (docs/POST_PIPELINE.md)."""
    import json as _json

    from spacemesh_tpu.tools import profiler

    assert profiler.main(["--pipeline", "--n", "2",
                          "--pipeline-labels", "512",
                          "--pipeline-batch", "256"]) == 0
    doc = _json.loads(capsys.readouterr().out)
    assert doc["labels_per_sec"] > 0
    assert set(doc["stages"]) >= {"dispatch_s", "fetch_s",
                                  "write_stall_s", "write_s"}
    assert doc["stages"]["batches"] == 2
    assert doc["bottleneck"] in ("dispatch_s", "fetch_s", "write_stall_s")
