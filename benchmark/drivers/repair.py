"""Repair traffic: one lost shard per verb, regenerated by `ec.rebuild`,
back to back.

Set-up writes the volume with the benchmark's needle writer, seals it
through the verb (`-kind` from the configuration) and records the CRC-32C
of every shard file; the check sets those against the reference encode
of the .dat once the window has closed.  The mix names the shards lost
(`lost_shards`), one per verb in an order the seed draws, every seed the
same set.  Every verb starts from the placement the seal left: the
rebuilder (the server holding most shards) keeps the shard it rebuilt,
so between verbs the re-arm moves that shard back to the server the
seal put it on (its sealed bytes copied in and mounted there) before the
next shard is lost through the unmount and delete RPCs.  Without that
the rebuilt shards gather on the rebuilder and later verbs copy fewer
helpers than a first loss does (PR 22's first chip runs: 0.107 and
0.122 GB/s on two sets of lost shards).  Set-up rebuilds each shard of
the set once, which compiles the repair program of each lost shard id.
A verb is complete when the master sees all shards mounted again; its
rebuilt shard is checked against the sealed one, and its rebuild plan
against the expected one."""

from __future__ import annotations

import os
import shutil

import google_crc32c
import numpy as np

from seaweedfs_tpu import shell
from seaweedfs_tpu.shell.command_ec import do_ec_encode, do_ec_rebuild

from .. import cluster as cl
from .. import core, fixture
from ..reference import clay, layout, rs
from . import verbs


def _n(cfg: dict) -> int:
    return cfg["data_shards"] + cfg["parity_shards"]


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
    with core.span("seal"):
        do_ec_encode(env, vol.vid, vol.collection,
                     data_shards=cfg["data_shards"],
                     parity_shards=cfg["parity_shards"],
                     kind=cfg["code_kind"])
        cl.settle(c, env, vol.vid, set(range(_n(cfg))), False)
    paths = cl.shard_paths(c, vol.collection, vol.vid)
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
    vol, c, env = run.state["vol"], run.state["cluster"], run.state["env"]
    try:
        cl.settle(c, env, vol.vid, set(range(_n(run.config))), False,
                  timeout=10)
        rec["complete"] = True
    except TimeoutError as e:
        core.log(f"rebuild incomplete: {e}")
        rec["complete"] = False
    rec["bytes"] = run.state["dat_bytes"]
    lost = run.state["lost"]
    rec["lost"] = lost
    path = cl.shard_paths(c, vol.collection, vol.vid).get(lost)
    rec["crc"] = verbs.crc_file(path) if path else None
    out = rec.get("out") or {}
    rec["rebuilt"] = out.get("rebuilt")
    rec["plan_kind"] = out.get("rebuild_stats", {}).get("plan_kind")


def restore(run, shard: int) -> None:
    """Put a rebuilt shard back where the seal placed it."""
    vol, c, env = run.state["vol"], run.state["cluster"], run.state["env"]
    home = run.state["home"][shard]
    if cl.shard_paths(c, vol.collection, vol.vid).get(shard) == home:
        return
    cl.drop_shards(c, env, vol.collection, vol.vid, [shard])
    shutil.copyfile(run.state["copies"][shard], home)
    i = cl.server_index(c, os.path.dirname(home))
    env.volume_server(c.volume_servers[i].grpc_address).call(
        "VolumeEcShardsMount", {"volume_id": vol.vid,
                                "collection": vol.collection,
                                "shard_ids": [shard]})


def rearm(run) -> None:
    vol, c, env = run.state["vol"], run.state["cluster"], run.state["env"]
    if "lost" in run.state:
        restore(run, run.state["lost"])
    rot = run.state["rotation"]
    lost = rot[run.state["turn"] % len(rot)]
    run.state["turn"] += 1
    run.state["lost"] = lost
    cl.drop_shards(c, env, vol.collection, vol.vid, [lost])
    cl.settle(c, env, vol.vid, set(range(_n(run.config))) - {lost}, False)


def window(run) -> core.Window:
    return verbs.loop(run, rebuild, after, rearm)


def expected_crcs(run) -> dict[int, int]:
    """The reference: every shard of the .dat under the configured code."""
    cfg, vol = run.config, run.state["vol"]
    k, m, small = cfg["data_shards"], cfg["parity_shards"], \
        cfg["small_block_size"]
    with core.span("reference"):
        data = layout.data_shards(vol.dat(), k, cfg["large_block_size"],
                                  small)
        if cfg["code_kind"] == "clay":
            code = clay.Clay(k, m)
            sym = [clay.to_layers(row, code.alpha, small) for row in data]
            par = code.encode(np.stack(sym))
            parity = [clay.from_layers(p, small) for p in par]
        else:
            parity = list(rs.encode(data, m))
    return {i: google_crc32c.value(row.tobytes())
            for i, row in enumerate(list(data) + list(parity))}


def check(run, w: core.Window) -> list[core.Compared]:
    want = expected_crcs(run)
    sealed = run.state["sealed"]
    plan = run.expect["plan_kind"]
    return [
        core.Compared("sealed_shards_differing",
                      sum(sealed.get(s) != crc for s, crc in want.items()),
                      0),
        core.Compared("rebuilt_shards_differing",
                      sum(v["crc"] != want[v["lost"]]
                          or v["rebuilt"] != [v["lost"]]
                          for v in w.verbs), 0),
        core.Compared(f"plan_not_{plan}",
                      sum(v["plan_kind"] != plan for v in w.verbs), 0),
        core.Compared("verbs_incomplete",
                      w.failed + sum(not v["complete"] for v in w.verbs),
                      0)]


def end_to_end(w: core.Window) -> dict:
    return {"repair_gbps": verbs.rate_gbps(w)}
