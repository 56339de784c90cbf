"""Multi-identity node: N smeshers in one App (BASELINE config 5 shape).

The reference registers many signers into one activation.Builder and runs
per-signer goroutines (activation.go:218 Register, node_identities.go).
Here: one standalone node hosts 4 identities, each POST-inits, publishes
its own ATX per epoch (shared in-proc poet round), and participates in
hare/beacon/certifier with its own eligibility. Proving goes through the
OUT-OF-PROCESS worker (PostSupervisor + RemotePostClient) to exercise the
node-side seam end to end.
"""

import asyncio

import pytest

from spacemesh_tpu.node import clock as clock_mod
from spacemesh_tpu.node.app import App
from spacemesh_tpu.node.config import load
from spacemesh_tpu.storage import atxs as atxstore
from spacemesh_tpu.storage import blocks as blockstore
from spacemesh_tpu.storage import layers as layerstore
from spacemesh_tpu.utils.vclock import VirtualClockLoop, cancel_all_tasks

LPE = 3
LAYER_SEC = 2.0  # virtual seconds (VirtualClockLoop)
N_IDS = 4


def _config(tmp_path):
    return load("standalone", overrides={
        "data_dir": str(tmp_path / "node"),
        "layer_duration": LAYER_SEC,
        "layers_per_epoch": LPE,
        "slots_per_layer": 2,
        "genesis": {"time": 0.0},  # replaced with virtual time in the run
        "post": {"labels_per_unit": 256, "scrypt_n": 2, "k1": 64, "k2": 8,
                 "k3": 4, "min_num_units": 1,
                 "pow_difficulty": "20" + "ff" * 31},
        "smeshing": {"start": True, "num_units": 1, "init_batch": 128,
                     "num_identities": N_IDS, "external_worker": True},
        "hare": {"committee_size": 40, "round_duration": 0.2,
                 "preround_delay": 0.5, "iteration_limit": 2},
        "beacon": {"proposal_duration": 0.2},
        "tortoise": {"hdist": 4, "window_size": 50},
    })


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("multiid")
    cfg = _config(tmp_path)
    loop = VirtualClockLoop()
    app = App(cfg, time_source=loop.time)

    async def go():
        await app.prepare()
        app.clock = clock_mod.LayerClock(loop.time() + 1.0,
                                         cfg.layer_duration,
                                         time_source=loop.time)
        await asyncio.wait_for(app.run(until_layer=2 * LPE + 1), 10_000)

    try:
        loop.run_until_complete(go())
        yield app
    finally:
        loop.run_until_complete(cancel_all_tasks())
        app.close()


def test_n_identities_created(ran):
    assert len(ran.signers) == N_IDS
    assert len({s.node_id for s in ran.signers}) == N_IDS
    assert len(ran.atx_builders) == N_IDS


def test_every_identity_publishes_atx_per_epoch(ran):
    for epoch in (0, 1):
        for s in ran.signers:
            atx = atxstore.by_node_in_epoch(ran.state, s.node_id, epoch)
            assert atx is not None, (
                f"identity {s.node_id.hex()[:8]} missing epoch-{epoch} ATX")
            assert atx.vrf_public_key == s.node_id


def test_external_worker_was_used(ran):
    assert ran.post_supervisor is not None
    assert ran.post_supervisor.alive()
    from spacemesh_tpu.post.remote import RemotePostClient

    for b in ran.atx_builders:
        assert isinstance(b.post_client, RemotePostClient)


def test_consensus_progressed_with_split_weight(ran):
    """With weight split over N identities, hare still reaches threshold
    (all identities vote) and blocks get applied."""
    applied = layerstore.last_applied(ran.state)
    assert applied >= LPE + 1
    assert any(blockstore.ids_in_layer(ran.state, lyr)
               for lyr in range(LPE, applied + 1))


def test_external_worker_refused_on_an_accelerator(tmp_path, monkeypatch):
    """One process per chip: where JAX lands on an accelerator, the node
    (which inits POST in-process) refuses to also babysit a worker that
    needs the same chip. The platform decides, not an env var — the run
    above is the CPU side of the same check."""
    import jax

    app = App(_config(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        with pytest.raises(RuntimeError, match="ONE process owns the chip"):
            asyncio.run(app.start_smeshing())
        assert app.post_supervisor is None
    finally:
        app.close()
