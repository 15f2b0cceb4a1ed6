"""Seal traffic: `ec.encode` of one full volume, back to back.

Set-up writes the volume's .dat/.idx with the benchmark's own needle
writer into the first server's directory before the cluster starts, and
seals it once (warm-up: every dispatch shape, the last partial batch
included).  Between verbs the shards are deleted everywhere and the
volume restored from its pristine copy by hard link: the program opens
.dat/.idx read-write but a sealed volume is frozen, and the check
proves the pristine files unchanged after every run.

A verb is complete when the master sees all k+m shards mounted and the
plain volume gone.  Checked per verb, off the clock: the CRC-32C of
every shard file and .ecx against the reference encode of the .dat
(benchmark/reference), and after the window that the encode work ran on
the expected codec and no other."""

from __future__ import annotations

import os

import google_crc32c

from seaweedfs_tpu import shell
from seaweedfs_tpu.shell.command_ec import do_ec_encode

from .. import cluster as cl
from .. import core, fixture
from ..reference import layout, rs
from . import verbs

CPU_CODECS = ("rs_native", "rs_numpy")


def _n_shards(cfg: dict) -> int:
    return cfg["data_shards"] + cfg["parity_shards"]


def setup(run) -> None:
    cfg = run.config
    vol = fixture.make_volume(cfg, run.seed)
    c = cl.make(run, cfg["volume_servers"])
    pristine = os.path.join(run.scratch, "pristine",
                            cl.base_name(vol.collection, vol.vid))
    os.makedirs(os.path.dirname(pristine))
    with core.span("fixture"):
        vol.write(pristine)
    src = os.path.join(cl.server_dir(c, 0),
                       cl.base_name(vol.collection, vol.vid))
    _link(pristine, src)
    c.start()
    env = shell.CommandEnv(c.master_grpc)
    cl.settle(c, env, vol.vid, set(), True)
    run.state.update(vol=vol, env=env, pristine=pristine,
                     src=src, src_grpc=c.volume_servers[0].grpc_address)
    with core.span("warmup"):
        seal(run)
        rearm(run)


def _link(pristine: str, dst: str) -> None:
    for ext in (".dat", ".idx"):
        if os.path.exists(dst + ext):
            os.remove(dst + ext)
        os.link(pristine + ext, dst + ext)


def seal(run) -> dict:
    vol = run.state["vol"]
    return do_ec_encode(run.state["env"], vol.vid, vol.collection)


def after(run, rec: dict) -> None:
    """Off the clock: did the verb finish, and what did it write?"""
    vol, c, env = run.state["vol"], run.state["cluster"], run.state["env"]
    n = _n_shards(run.config)
    try:
        cl.settle(c, env, vol.vid, set(range(n)), False, timeout=10)
        rec["complete"] = True
    except TimeoutError as e:
        core.log(f"seal incomplete: {e}")
        rec["complete"] = False
    rec["bytes"] = os.path.getsize(run.state["pristine"] + ".dat")
    paths = cl.shard_paths(c, vol.collection, vol.vid)
    rec["crcs"] = {s: verbs.crc_file(p) for s, p in paths.items()}
    stem = cl.base_name(vol.collection, vol.vid) + ".ecx"
    rec["ecx_crcs"] = [verbs.crc_file(os.path.join(cl.server_dir(c, i),
                                                   stem))
                       for i in range(len(c.volume_servers))
                       if os.path.exists(os.path.join(cl.server_dir(c, i),
                                                      stem))]


def rearm(run) -> None:
    vol, c, env = run.state["vol"], run.state["cluster"], run.state["env"]
    cl.drop_shards(c, env, vol.collection, vol.vid,
                   list(range(_n_shards(run.config))))
    _link(run.state["pristine"], run.state["src"])
    env.volume_server(run.state["src_grpc"]).call(
        "VolumeMount", {"volume_id": vol.vid})
    cl.settle(c, env, vol.vid, set(), True)


def window(run) -> core.Window:
    return verbs.loop(run, seal, after, rearm)


def expected_crcs(run) -> tuple[dict[int, int], int]:
    """The reference: shard CRCs of the volume's .dat under RS(k, m) in
    the SeaweedFS layout, and the .ecx's."""
    cfg, vol = run.config, run.state["vol"]
    with core.span("reference"):
        data = layout.data_shards(vol.dat(), cfg["data_shards"],
                                  cfg["large_block_size"],
                                  cfg["small_block_size"])
        parity = rs.encode(data, cfg["parity_shards"])
    crcs = {i: google_crc32c.value(row.tobytes())
            for i, row in enumerate(list(data) + list(parity))}
    return crcs, google_crc32c.value(vol.ecx())


def check(run, w: core.Window) -> list[core.Compared]:
    want, want_ecx = expected_crcs(run)
    vol = run.state["vol"]
    shard_bad = sum(v["crcs"].get(s) != crc
                    for v in w.verbs for s, crc in want.items())
    ecx_bad = sum((not v["ecx_crcs"])
                  + sum(x != want_ecx for x in v["ecx_crcs"])
                  for v in w.verbs)
    pristine_crc = verbs.crc_file(run.state["pristine"] + ".dat")
    out = [core.Compared("shards_differing", shard_bad, 0),
           core.Compared("ecx_differing", ecx_bad, 0),
           core.Compared("verbs_incomplete",
                         w.failed + sum(not v["complete"]
                                        for v in w.verbs), 0),
           core.Compared("pristine_changed", float(
               pristine_crc != google_crc32c.value(vol.dat().tobytes())),
               0)]
    backend = run.expect["encode_backend"]
    out += core.dispatch_compared(w, "encode", backend)
    ran = core.dispatches(w)
    out.append(core.Compared("cpu_codec_dispatches", sum(
        v for (b, _), v in ran.items() if b in CPU_CODECS
        and b != backend), 0))
    return out


def end_to_end(w: core.Window) -> dict:
    return {"seal_gbps": verbs.rate_gbps(w)}
