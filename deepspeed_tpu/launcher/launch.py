"""Node-local launcher (reference: ``launcher/launch.py:90-214`` — decode
world info, compute the global rank mapping, export the rendezvous env, fork
one process per local slot, then babysit: if any child dies, kill the rest
and propagate the exit code; SIGTERM/SIGINT are forwarded to children).

Env contract written for each child (consumed by ``comm.init_distributed``):
  COORDINATOR_ADDRESS  host:port for jax.distributed.initialize
  NUM_PROCESSES        world size (total processes across hosts)
  PROCESS_ID           this child's global rank
  LOCAL_RANK           this child's slot on this host

This parent never initialises a JAX backend (it only imports the package),
so its children find the host's chips free. On a TPU host that means ONE
child: a JAX process takes every local chip, nothing here divides a host's
chips among several, and a second child dies at backend init — so more than
one slot on a TPU host is refused with the reason (:func:`_refuse_chip_sharing`).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List

from ..utils.logging import logger
from .runner import decode_world_info


def parse_args(args=None):
    parser = argparse.ArgumentParser(prog="deepspeed_tpu.launcher.launch")
    parser.add_argument("--world_info", type=str, required=True)
    parser.add_argument("--node_rank", type=int, default=0)
    parser.add_argument("--master_addr", type=str, default="127.0.0.1")
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


def global_rank_mapping(world_info: Dict[str, List[int]]) -> Dict[str, List[int]]:
    """Assign consecutive global ranks host by host (reference :113-123)."""
    mapping: Dict[str, List[int]] = {}
    rank = 0
    for host, slots in world_info.items():
        mapping[host] = []
        for _ in slots:
            mapping[host].append(rank)
            rank += 1
    return mapping


def _tpu_device_nodes() -> List[str]:
    """The host's TPU device nodes, found without touching JAX (a v5e VM
    exposes its chips under /dev/vfio; older generations as /dev/accel*)."""
    import glob
    return sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))


def _refuse_chip_sharing(n_local: int) -> None:
    """More than one slot on a host whose children would run on its TPU
    chips cannot work: every child's JAX takes ALL local chips, and all
    but the first fail with "Unable to initialize backend 'tpu': ABORTED:
    Internal error when accessing libtpu multi-process lockfile" (v5e,
    PR 21). Dividing a host's chips among processes is not implemented;
    one process per host drives all of them (the mesh's dp/tp/pp axes
    divide the chips)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if n_local <= 1 or platforms.split(",")[0] == "cpu":
        return
    nodes = _tpu_device_nodes()
    if nodes:
        raise SystemExit(
            f"launch: {n_local} slots asked on a host with TPU chips "
            f"({', '.join(nodes)}): a JAX process takes every local chip, so "
            f"all but the first child would die at backend init. Launch ONE "
            f"slot per TPU host (it drives all local chips through the "
            f"mesh), or set JAX_PLATFORMS=cpu for a CPU rehearsal.")


def main(args=None):
    args = parse_args(args)
    world_info = decode_world_info(args.world_info)
    hosts = list(world_info.keys())
    node_host = hosts[args.node_rank]
    local_slots = world_info[node_host]
    rank_map = global_rank_mapping(world_info)
    world_size = sum(len(s) for s in world_info.values())

    logger.info(f"node {args.node_rank} ({node_host}): slots={local_slots}, "
                f"world_size={world_size}")
    _refuse_chip_sharing(len(local_slots))

    children: List[subprocess.Popen] = []
    for local_rank, slot in enumerate(local_slots):
        env = os.environ.copy()
        env["COORDINATOR_ADDRESS"] = f"{args.master_addr}:{args.master_port}"
        env["NUM_PROCESSES"] = str(world_size)
        env["PROCESS_ID"] = str(rank_map[node_host][local_rank])
        env["LOCAL_RANK"] = str(local_rank)
        env["LOCAL_SLOT"] = str(slot)
        cmd = [sys.executable, "-u", args.user_script] + list(args.user_args)
        children.append(subprocess.Popen(cmd, env=env))

    # forward termination signals to the whole brood
    def _forward(signum, frame):
        for p in children:
            if p.poll() is None:
                p.send_signal(signum)

    signal.signal(signal.SIGTERM, _forward)
    signal.signal(signal.SIGINT, _forward)

    # babysitter: any failure kills all siblings and propagates the code
    # (reference :176-214)
    exit_code = 0
    try:
        while children:
            alive = []
            for p in children:
                rc = p.poll()
                if rc is None:
                    alive.append(p)
                elif rc != 0:
                    logger.error(f"child {p.pid} failed with code {rc}; "
                                 "terminating siblings")
                    exit_code = rc
                    for q in children:
                        if q is not p and q.poll() is None:
                            q.terminate()
                    for q in children:
                        if q is not p:
                            try:
                                q.wait(timeout=30)
                            except subprocess.TimeoutExpired:
                                q.kill()
                    return exit_code
            children = alive
            if children:
                time.sleep(0.25)
    finally:
        for p in children:
            if p.poll() is None:
                p.terminate()
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
