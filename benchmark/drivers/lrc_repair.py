"""LRC repair traffic: repair.py's verbs, re-arm and window on an LRC
volume, one lost fragment per `ec.rebuild`, back to back.

Set-up seals the volume through the verb with the configuration's
`lrc_locals`, and refuses to go on unless the sealed .vif records the
configuration's `lrc_construction`: a program that seals LRC under other
global coefficients cannot run this cell.  The check sets every sealed
fragment against reference/lrc.py's encode of the .dat, every rebuilt
fragment against the XOR of its local group in the reference, and the
fragments each rebuild read against that group."""

from __future__ import annotations

import json
import os
import shutil

import google_crc32c

from seaweedfs_tpu import shell
from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild

from .. import cluster as cl
from .. import core, fixture
from ..reference import layout, lrc
from . import repair, verbs
from .repair import end_to_end, rearm  # noqa: F401  (the driver contract)


def setup(run) -> None:
    cfg = run.config
    vol = fixture.make_volume(cfg, run.seed)
    c = cl.make(run, cfg["volume_servers"])
    base = os.path.join(cl.server_dir(c, 0),
                        cl.base_name(vol.collection, vol.vid))
    with core.span("fixture"):
        vol.write(base)
    c.start()
    env = shell.CommandEnv(c.master_grpc)
    cl.settle(c, env, vol.vid, set(), True)
    run.state.update(vol=vol, env=env,
                     dat_bytes=os.path.getsize(base + ".dat"))
    n = cfg["data_shards"] + cfg["parity_shards"]
    with core.span("seal"):
        do_ec_encode(env, vol.vid, vol.collection,
                     data_shards=cfg["data_shards"],
                     parity_shards=cfg["parity_shards"],
                     kind=cfg["code_kind"], lrc_locals=cfg["lrc_locals"])
        cl.settle(c, env, vol.vid, set(range(n)), False)
    paths = cl.shard_paths(c, vol.collection, vol.vid)
    with open(os.path.splitext(paths[0])[0] + ".vif") as f:
        sealed = json.load(f).get("lrc_construction")
    if sealed != cfg["lrc_construction"]:
        raise RuntimeError(
            f"the program sealed LRC with construction {sealed!r}, the "
            f"configuration states {cfg['lrc_construction']!r}")
    run.state["sealed"] = {s: verbs.crc_file(p) for s, p in paths.items()}
    run.state["home"] = paths
    where: dict[str, list[int]] = {}
    for s, p in sorted(paths.items()):
        where.setdefault(os.path.basename(os.path.dirname(p)), []).append(s)
    core.log(f"placement after the seal: {dict(sorted(where.items()))}")
    lost = fixture.rng(run.seed, 4).permutation(run.mix["lost_shards"])
    run.state["rotation"] = [int(s) for s in lost]
    run.state["turn"] = 0
    os.makedirs(os.path.join(run.scratch, "sealed"))
    run.state["copies"] = {}
    for s in run.state["rotation"]:
        copy = os.path.join(run.scratch, "sealed", os.path.basename(paths[s]))
        shutil.copyfile(paths[s], copy)
        run.state["copies"][s] = copy
    with core.span("warmup"):
        for _ in run.state["rotation"]:
            rearm(run)
            rebuild(run)
            after(run, {})
    rearm(run)


def rebuild(run) -> dict:
    vol = run.state["vol"]
    return do_ec_rebuild(run.state["env"], vol.vid, vol.collection)


def after(run, rec: dict) -> None:
    repair.after(run, rec)
    out = rec.get("out") or {}
    rec["read_shards"] = out.get("rebuild_stats", {}).get("read_shards")
    rec["copied"] = out.get("copied")


def window(run) -> core.Window:
    return verbs.loop(run, rebuild, after, rearm)


def reference_shards(run) -> dict:
    """Every fragment of the .dat under the reference LRC."""
    cfg, vol = run.config, run.state["vol"]
    k, l = cfg["data_shards"], cfg["lrc_locals"]
    with core.span("reference"):
        data = layout.data_shards(vol.dat(), k, cfg["large_block_size"],
                                  cfg["small_block_size"])
        parity = lrc.encode(data, l, cfg["parity_shards"] - l)
    return dict(enumerate(list(data) + list(parity)))


def check(run, w: core.Window) -> list[core.Compared]:
    cfg = run.config
    k, l = cfg["data_shards"], cfg["lrc_locals"]
    shards = reference_shards(run)
    want = {i: google_crc32c.value(row.tobytes())
            for i, row in shards.items()}
    repaired = {s: google_crc32c.value(
        lrc.local_repair(shards, s, k, l).tobytes())
        for s in run.state["rotation"]}
    sealed = run.state["sealed"]
    plan = run.expect["plan_kind"]
    return [
        core.Compared("sealed_shards_differing",
                      sum(sealed.get(s) != crc for s, crc in want.items()),
                      0),
        core.Compared("rebuilt_shards_differing",
                      sum(v["crc"] != repaired[v["lost"]]
                          or v["rebuilt"] != [v["lost"]]
                          for v in w.verbs), 0),
        core.Compared(f"plan_not_{plan}",
                      sum(v["plan_kind"] != plan for v in w.verbs), 0),
        core.Compared("reads_outside_local_group",
                      sum(sorted(v["read_shards"] or [])
                          != lrc.local_group(v["lost"], k, l)
                          for v in w.verbs), 0),
        core.Compared("verbs_incomplete",
                      w.failed + sum(not v["complete"] for v in w.verbs),
                      0)]
