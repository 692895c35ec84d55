"""Named scopes of the shard programs (subprocess, 4 host devices).

For nap, multistep and standard, forward and transpose, ELL and COO
local products, on Topology(2, 2): the compiled program's HLO carries
``repro.exchange.<phase>`` on the all-to-all of every phase the method
runs (and on no other all-to-all), ``repro.buffers`` and ``repro.local``
on some instruction, and the slot loop (ELL) under ``repro.local``; the
forward program's packing gather of every phase carries its phase.  The
forward ELL program, whose column ids are composed with the buffer
gathers, runs no gather under ``repro.buffers`` (what is left there is
the staged concatenate of the NAP exchange; the standard one has none),
while the COO program still gathers its buffers there.
Under ``integrity="detect"`` each phase's checksum all-to-all carries
the phase's scope and the ABFT ops ``repro.abft``.
Prints one line per program and ``SPANS OK`` at the end.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import re

import numpy as np

import repro.api as nap
from repro.core.topology import Topology
from repro.sparse import random_fixed_nnz

PHASES = {"nap": {"full", "init", "inter", "final"},
          "multistep": {"full", "init", "inter", "final", "direct"},
          "standard": {"pair"}}
SCOPE = re.compile(r'op_name="[^"]*?(repro\.[^/"]+)')


def scoped(text: str, opcode: str):
    """Scope of every instruction of ``opcode`` ("" where it has none)."""
    out = []
    for line in text.splitlines():
        if re.search(rf" {opcode}\(", line):
            m = SCOPE.search(line)
            out.append(m.group(1) if m else "")
    return out


def main():
    a = random_fixed_nnz(256, 6, seed=1)
    x = np.ones(256, np.float32)
    for comm, phases in PHASES.items():
        for fmt in ("ell", "coo"):
            op = nap.operator(a, topo=Topology(2, 2), comm=comm,
                              local_compute=fmt, cache=False)
            op @ x
            op.T @ x
            ex = op.executor
            for direction in ("forward", "transpose"):
                run = ex._runs[direction]
                pad = (ex.compiled.cols_pad if direction == "forward"
                       else ex.compiled.rows_pad)
                shards = np.zeros((2, 2, pad, 1), np.float32)
                text = run.jitted.lower(shards, *run.args()).compile() \
                    .as_text()
                a2a = scoped(text, "all-to-all")
                assert sorted(a2a) == sorted(f"repro.exchange.{p}"
                                             for p in phases), a2a
                found = set(SCOPE.findall(text))
                composed = (direction == "forward"
                            and run.local_compute == "ell")
                want = {"repro.local"}
                if not (composed and comm == "standard"):
                    want.add("repro.buffers")
                assert want <= found, found
                if direction == "forward":
                    gathers = set(scoped(text, "gather"))
                    assert {f"repro.exchange.{p}"
                            for p in phases} <= gathers, gathers
                    assert ("repro.buffers" in gathers) != composed, gathers
                if run.local_compute == "ell":
                    assert set(scoped(text, "while")) == {"repro.local"}
                print(comm, fmt, direction, run.local_compute,
                      sorted(found), flush=True)
    # integrity: each phase's checksum words ride a second all-to-all in
    # the phase's scope; the ABFT triple has its own
    op = nap.operator(a, topo=Topology(2, 2), comm="nap", local_compute="ell",
                      integrity="detect", cache=False)
    op @ x
    run = op.executor._runs["forward"]
    shards = np.zeros((2, 2, op.executor.compiled.cols_pad, 1), np.float32)
    text = run.jitted.lower(shards, *run.args()).compile().as_text()
    assert sorted(scoped(text, "all-to-all")) == sorted(
        2 * [f"repro.exchange.{p}" for p in PHASES["nap"]])
    assert "repro.abft" in SCOPE.findall(text)
    print("SPANS OK", flush=True)


if __name__ == "__main__":
    main()
