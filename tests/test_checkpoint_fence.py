"""The checkpoint store's fence gate reads the stored envelope's head.

`FencedCheckpointStore.save` checks a writer's fence against the
stored envelope's `fence` and `owner`, which `save` writes ahead of
the state. It reads only the head of the file; an envelope of any
other form falls back to a full `load`, counted by
`checkpoint_fence_full_loads_total{role}`. The fence rule itself is
the one every fenced write path shares (`queue._check_fence`).
"""

import json
import os

import pytest

from fluidframework_tpu.server import queue as Q
from fluidframework_tpu.server.queue import FencedCheckpointStore, \
    FencedError
from fluidframework_tpu.utils import metrics as M

STATE = {"offset": 7, "docs": {f"doc{i}": [i, i + 1] for i in range(64)}}
LONG_OWNER = "w" * (2 * Q._HEAD_BYTES)


@pytest.fixture
def reg():
    reg = M.MetricsRegistry()
    old = M.set_registry(reg)
    yield reg
    M.set_registry(old)


def full_loads(reg, key="p0") -> float:
    return reg.counter("checkpoint_fence_full_loads_total", role=key).value


def head_of(store, key="p0"):
    with open(store._path(key)) as f:
        return Q._envelope_head(f.read(Q._HEAD_BYTES))


def no_full_load(monkeypatch):
    def refuse(self, key):
        raise AssertionError("the fence check loaded the whole envelope")

    monkeypatch.setattr(FencedCheckpointStore, "load", refuse)


@pytest.mark.parametrize("owner", [
    None, "B", 'a "quoted" \\ back\\slash\n owner: é 中 \U0001f600',
    LONG_OWNER,
], ids=["none", "plain", "escaped", "longer-than-head"])
def test_head_matches_full_load(tmp_path, reg, owner):
    store = FencedCheckpointStore(str(tmp_path))
    store.save("p0", STATE, fence=3, owner=owner)
    env = store.load("p0")
    assert env == {"fence": 3, "owner": owner, "state": STATE}
    if owner is LONG_OWNER:
        assert head_of(store) is None  # the owner runs past the head
    else:
        assert head_of(store) == (env["fence"], env["owner"])
    assert full_loads(reg) == 0  # the first save found no file

    # The same writer saves again: the head (or the fallback) binds it.
    store.save("p0", {"offset": 8}, fence=3, owner=owner)
    assert full_loads(reg) == (1 if owner is LONG_OWNER else 0)
    # Another owner at the same fence is rejected on either path
    # (None binds nothing, so there any writer at that fence passes).
    if owner is not None:
        with pytest.raises(FencedError):
            store.save("p0", {"offset": 0}, fence=3, owner="Z")
        assert store.load("p0")["state"] == {"offset": 8}


@pytest.mark.parametrize("text", [
    '{"owner": "A", "fence": 5, "state": {}}',
    '{"fence":5,"owner":"A","state":{}}',
    '{"fence": 5.0, "owner": "A", "state": {}}',
    '{"fence": true, "owner": "A", "state": {}}',
    '{"fence": 5, "owner": 7, "state": {}}',
    '{"fence": 5, "owner": "A", "other": 1, "state": {}}',
    '{"fence": 5, "owner": "A"}',
    '{"fence": 5',
    '{"fence": 5, "owner": "A',
    '',
], ids=["key-order", "compact", "float-fence", "bool-fence", "int-owner",
        "extra-key", "no-state", "torn-fence", "torn-owner", "empty"])
def test_head_of_another_form_does_not_parse(text):
    assert Q._envelope_head(text) is None


def test_head_parse_stops_at_its_literals():
    assert Q._envelope_head('{"fence": -2, "owner": null, "state": ') == \
        (-2, None)
    assert Q._envelope_head(
        json.dumps({"fence": 12, "owner": "x", "state": [1]})) == (12, "x")


def test_other_key_order_falls_back_and_counts(tmp_path, reg):
    store = FencedCheckpointStore(str(tmp_path))
    with open(store._path("p0"), "w") as f:
        json.dump({"owner": "A", "fence": 5, "state": STATE}, f)
    with pytest.raises(FencedError):
        store.save("p0", {"offset": 0}, fence=4, owner="A")
    assert full_loads(reg) == 1
    assert store.load("p0")["state"] == STATE
    with pytest.raises(FencedError):
        store.save("p0", {"offset": 0}, fence=5, owner="B")
    assert full_loads(reg) == 2
    store.save("p0", {"offset": 9}, fence=5, owner="A")
    assert full_loads(reg) == 3
    # Rewritten in the store's own form: the head decides from now on.
    assert head_of(store) == (5, "A")
    store.save("p0", {"offset": 10}, fence=6, owner="B")
    assert full_loads(reg) == 3
    assert store.load("p0") == {"fence": 6, "owner": "B",
                                "state": {"offset": 10}}


@pytest.mark.parametrize("text", ["", '{"fence": 7', "not json"],
                         ids=["empty", "torn", "garbage"])
def test_unreadable_envelope_means_no_check(tmp_path, reg, text):
    """As before the head read: no readable envelope, no check."""
    store = FencedCheckpointStore(str(tmp_path))
    with open(store._path("p0"), "w") as f:
        f.write(text)
    store.save("p0", {"offset": 1}, fence=1, owner="A")
    assert full_loads(reg) == 1
    assert store.load("p0")["state"] == {"offset": 1}


def test_valid_head_decides_over_a_damaged_body(tmp_path, reg):
    store = FencedCheckpointStore(str(tmp_path))
    with open(store._path("p0"), "w") as f:
        f.write('{"fence": 9, "owner": "B", "state": {"offset": ')
    assert store.load("p0") is None
    with pytest.raises(FencedError):
        store.save("p0", {"offset": 1}, fence=8, owner="A")
    store.save("p0", {"offset": 2}, fence=10, owner="A")
    assert full_loads(reg) == 0
    assert store.load("p0")["state"] == {"offset": 2}


def test_deposed_writers_rejected_without_full_load(tmp_path, reg,
                                                    monkeypatch):
    store = FencedCheckpointStore(str(tmp_path))
    store.save("p0", STATE, fence=4, owner="B")
    path = store._path("p0")
    with open(path) as f:
        stored = f.read()
    no_full_load(monkeypatch)
    with pytest.raises(FencedError):
        store.save("p0", {"offset": 3}, fence=3, owner="A")  # lower fence
    with pytest.raises(FencedError):
        store.save("p0", {"offset": 3}, fence=4, owner="A")  # equal, other
    with open(path) as f:
        assert f.read() == stored
    assert full_loads(reg) == 0
    assert sorted(os.listdir(str(tmp_path))) == ["p0.ckpt.json",
                                                 "p0.ckpt.json.lock"]


def test_normal_path_never_loads_whole_envelope(tmp_path, reg,
                                                monkeypatch):
    store = FencedCheckpointStore(str(tmp_path))
    no_full_load(monkeypatch)
    n = store.save("p0", STATE, fence=1, owner="A")  # no file yet
    for fence, owner in ((1, "A"), (2, "B"), (2, "B"), (5, None), (5, "C")):
        n = store.save("p0", {**STATE, "offset": fence}, fence=fence,
                       owner=owner)
    assert full_loads(reg) == 0
    assert os.path.getsize(store._path("p0")) == n
    assert head_of(store) == (5, "C")


def test_counter_on_metrics_endpoint(tmp_path, reg):
    """The counter exists, at 0, from a key's first save, so /metrics
    shows that the fast path engages."""
    store = FencedCheckpointStore(str(tmp_path))
    store.save("deli", STATE, fence=1, owner="A")
    store.save("deli", STATE, fence=1, owner="A")
    text = reg.to_prometheus()
    line = [ln for ln in text.splitlines()
            if ln.startswith("fluid_checkpoint_fence_full_loads_total{")
            or ln.startswith("checkpoint_fence_full_loads_total{")]
    assert len(line) == 1 and 'role="deli"' in line[0]
    assert float(line[0].rsplit(" ", 1)[1]) == 0
