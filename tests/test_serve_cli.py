"""``serve.py``'s entry point, as a table: which of the six serving modes
an argv selects, the three combinations ``serve.main`` refuses before it
builds anything, and the three engine settings ``ServeOptions`` rejects.
Every benchmark cell starts through this dispatch (``--synthetic`` with
no fleet flag: ``single``)."""

import argparse

import pytest

import serve
from mx_rcnn_tpu.serve import ServeOptions


def _ns(**kw):
    base = dict(replica_index=-1, replicas=1, fabric=False, pool_file="",
                join="")
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.mark.parametrize("flags, mode", [
    # with every fabric flag dormant, the pre-fabric decision tree
    ({}, "single"),
    ({"replicas": 4}, "plane"),
    ({"replicas": 4, "replica_index": 2}, "replica"),
    # opt-in paths
    ({"fabric": True}, "fabric"),
    ({"pool_file": "/p"}, "fabric"),
    ({"join": "h:1"}, "member"),
    ({"fabric": True, "replicas": 2}, "fabric"),
    # the child check stays FIRST even under fabric flags
    ({"fabric": True, "replica_index": 0}, "replica"),
], ids=["no-flag", "replicas", "replicas+replica-index", "fabric", "pool-file",
        "join", "fabric+replicas", "fabric+replica-index"])
def test_choose_mode_dispatch_keeps_fork_plane_bit_identical(flags, mode):
    assert serve.choose_mode(_ns(**flags)) == mode


@pytest.fixture
def no_server(monkeypatch):
    """A refusal comes before any model, engine or socket exists."""
    def built(args):
        raise AssertionError("serve.main built a server it had to refuse")

    for name in ("main_single", "main_plane", "main_replica", "main_fabric",
                 "main_member", "main_multimodel"):
        monkeypatch.setattr(serve, name, built)


@pytest.mark.parametrize("argv, says", [
    (["--cascade", "small:big"], "--cascade routes between two --models"),
    (["--models", "a=resnet50,b=resnet50", "--replicas", "2"],
     "--models requires single-process mode (got mode 'plane')"),
    (["--stream", "--fabric"],
     "--stream requires single-process mode (got mode 'fabric'"),
], ids=["cascade-without-models", "models-outside-single",
        "stream-outside-single"])
def test_main_refuses_before_building(no_server, argv, says):
    args = serve.parse_args(["--synthetic", "--network", "resnet50"] + argv)
    with pytest.raises(SystemExit) as e:
        serve.main(args)
    assert says in str(e.value)


@pytest.mark.parametrize("fields, says", [
    ({"batch_size": 0}, "batch_size must be >= 1"),
    ({"batch_size": 8, "max_queue": 7},
     "max_queue (7) must be >= batch_size (8)"),
    ({"prep_workers": -1}, "prep_workers must be >= 0"),
], ids=["batch-under-one", "queue-under-batch", "negative-prep-workers"])
def test_serve_options_rejects(fields, says):
    with pytest.raises(ValueError) as e:
        ServeOptions(**fields)
    assert says in str(e.value)
