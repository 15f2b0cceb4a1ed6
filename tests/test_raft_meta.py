"""Raft term/vote persistence: meta.jsonl is an append journal whose last
whole line wins on load.  Appends keep a vote off the file system's
replace-over-existing flush, which under the node lock outlasts an
election timeout and livelocks elections."""

import json
import os

import pytest

from seaweedfs_tpu.master import raft as raft_mod
from seaweedfs_tpu.master.raft import RaftNode


def _node(state_dir):
    return RaftNode("n0", ["n0", "n1", "n2"], apply_fn=lambda c: None,
                    snapshot_fn=dict, restore_fn=lambda s: None,
                    state_dir=str(state_dir))


def _vote(node, term, voted_for):
    node.term, node.voted_for = term, voted_for
    node._persist_meta()


def test_votes_append_and_survive_restart(tmp_path):
    node = _node(tmp_path)
    meta = tmp_path / "meta.jsonl"
    _vote(node, 1, "n1")
    inode = os.stat(meta).st_ino
    _vote(node, 2, None)
    _vote(node, 2, "n2")
    # appended in place: same file, one record per write
    assert os.stat(meta).st_ino == inode
    assert len(meta.read_text().splitlines()) == 3
    again = _node(tmp_path)
    assert (again.term, again.voted_for) == (2, "n2")


def test_torn_tail_falls_back_and_is_rewritten(tmp_path):
    node = _node(tmp_path)
    _vote(node, 3, "n1")
    meta = tmp_path / "meta.jsonl"
    with open(meta, "a") as f:
        f.write('{"term":4,"vot')          # crash mid-append
    again = _node(tmp_path)
    assert (again.term, again.voted_for) == (3, "n1")
    # the next record must not land on the torn line
    _vote(again, 5, "n0")
    assert [json.loads(x) for x in meta.read_text().splitlines()] == \
        [{"term": 5, "voted_for": "n0"}]
    assert (_node(tmp_path).term, _node(tmp_path).voted_for) == (5, "n0")


def test_journal_folds_to_one_line(tmp_path, monkeypatch):
    monkeypatch.setattr(raft_mod, "_META_MAX_LINES", 4)
    node = _node(tmp_path)
    for term in range(1, 7):
        _vote(node, term, None)
    lines = (tmp_path / "meta.jsonl").read_text().splitlines()
    # writes 1-4 append, write 5 folds to one line, write 6 appends
    assert [json.loads(x)["term"] for x in lines] == [5, 6]
    assert _node(tmp_path).term == 6


@pytest.mark.parametrize("voted_for", [None, "n2"])
def test_legacy_single_record_meta_loads(tmp_path, voted_for):
    (tmp_path / "meta.json").write_text(
        json.dumps({"term": 7, "voted_for": voted_for}))
    node = _node(tmp_path)
    assert (node.term, node.voted_for) == (7, voted_for)
    _vote(node, 8, "n1")
    assert (_node(tmp_path).term, _node(tmp_path).voted_for) == (8, "n1")
