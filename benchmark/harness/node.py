"""A node as `tendermint-tpu node` builds it, in this process, with its
public surfaces opened on loopback ports."""

from __future__ import annotations

import contextlib
import json
import sys
import time
from urllib.request import urlopen


def build_node(home: str, chain_id: str, config: dict, *, genesis_json: str | None,
               trace: bool):
    """init + default_new_node with the configuration file's `node`
    section applied over the program's defaults ("section.key": value).
    Nothing else is set: every other knob is the program's default."""
    from tendermint_tpu.cmd import main as cli
    from tendermint_tpu.node import default_new_node

    with contextlib.redirect_stdout(sys.stderr):  # stdout is the result's
        if cli.main(["--home", home, "init", "--chain-id", chain_id]):
            raise RuntimeError("tendermint-tpu init failed")
    c = cli._load_config(home)
    for dotted, value in config.get("node", {}).items():
        section, key = dotted.split(".")
        if not hasattr(getattr(c, section), key):
            raise RuntimeError(f"the program's config has no [{section}] {key}")
        setattr(getattr(c, section), key, value)
    c.rpc.laddr = c.p2p.laddr = c.base.prof_laddr = "tcp://127.0.0.1:0"
    c.instrumentation.prometheus = True
    c.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
    c.instrumentation.tracing = bool(trace)
    if genesis_json is not None:
        with open(c.base.genesis_path(), "w") as f:
            f.write(genesis_json)
    return default_new_node(c)


class Surfaces:
    """The running node's HTTP surfaces."""

    def __init__(self, node):
        self.rpc_addr = node.rpc_listen_addr
        self.metrics_addr = node._metrics_server.listen_addr
        self.prof_addr = node._prof_server.listen_addr

    def debug(self, path: str) -> dict:
        with urlopen(f"http://{self.prof_addr}{path}", timeout=30) as r:
            return json.load(r)

    def wait_verifier(self, deadline_s: float) -> dict:
        """The verifier the node resolved, once its warm-up has ended."""
        end = time.monotonic() + deadline_s
        while True:
            v = self.debug("/debug/crypto")["verifier"]
            state = v.get("warmup")
            if state in ("ok", "disabled") or str(state).startswith("error"):
                return v
            if time.monotonic() > end:
                raise RuntimeError(f"verify warm-up still running after "
                                   f"{deadline_s:.0f}s: {v}")
            time.sleep(0.25)
