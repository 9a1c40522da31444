"""Whether two checkouts compile each cell's round step to the same
program. The step is compiled at the cell's size for a described TPU
v5e (no chip needed: one device, or a 2x2 mesh for a four-chip cell),
and the HLO texts are compared with what only names the source taken
out: ``metadata={...}``, the file and stack-frame tables, and the debug
locations inside Pallas kernel bodies.

    JAX_PLATFORMS=cpu python3 bench/hlo_same.py <other checkout> [cell ...]

Prints one line a cell and exits 1 when any differs. Each checkout is
compiled in a process of its own, with its own ``bench`` and ``src``.
"""
from __future__ import annotations

import base64
import hashlib
import os
import re
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CELLS = ("cifar10_cnn.paper", "femnist_cnn.topk_all", "cifar10_cnn.mesh4")
_META = re.compile(r",?\s*metadata=\{[^}]*\}")
_TABLES = re.compile(
    r"(?ms)^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def normalise(text: str) -> str:
    from jax._src.lib.mlir import ir

    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            return '"body":' + repr(
                module.operation.get_asm(enable_debug_info=False))
    return _BODY.sub(body, _TABLES.sub("", _META.sub("", text)))


def compiled_step(name: str) -> str:
    """HLO text of the cell's step compiled for a described v5e, from the
    checkout on ``sys.path``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from bench import system
    from bench.spec import resolve
    from repro.data.pipeline import FederatedData
    from repro.federated import FLServer
    from repro.federated import sharded
    from repro.federated.simulation import make_topology

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = resolve(name, root=Path(sys.path[0]))
    data = system.make_data(cell.config,
                            system.make_job(cell.config, cell.traffic), 1)
    flcfg = system.make_flconfig(cell.config, cell.traffic)
    fed = FederatedData(client_x=data.client_x, client_y=data.client_y,
                        ref_x=data.ref_x, ref_y=data.ref_y,
                        test_x=data.test_x, test_y=data.test_y,
                        n_classes=data.n_classes)
    tp = make_topology(flcfg)
    server = FLServer(flcfg, tp, fed, method=cell.traffic["method"], seed=1,
                      engine="jit")
    state, cdata = server._eng_state, server._eng_data
    if cell.chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
        step = server._eng.step
        args = (jax.tree.map(shape, state), jax.tree.map(shape, cdata),
                jax.ShapeDtypeStruct((), jnp.int32, weak_type=True,
                                     sharding=one))
    else:
        kc, pc = sharded.mesh_axes(tp.n_clouds, tp.n_clients, cell.chips)
        mesh = Mesh(np.array(topo.devices[:cell.chips]).reshape(kc, pc),
                    sharded.AXES)
        sharded.client_mesh = lambda *a, **k: mesh
        eng = sharded.compiled_sharded(sharded.ShardStatic(
            static=server._eng.static, kc=kc, pc=pc))
        step = next(c.cell_contents for c in eng.step.__closure__
                    if hasattr(c.cell_contents, "lower"))

        def shape(x, spec=PartitionSpec()):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=NamedSharding(mesh, spec))
        per_client = PartitionSpec(sharded.AXES)
        res = (per_client if state.res_client.shape[:1] == (tp.n_clients,)
               else PartitionSpec())
        args = (jax.tree.map(shape, state)._replace(
                    res_client=shape(state.res_client, res)),
                type(cdata)(client_x=shape(cdata.client_x, per_client),
                            client_y=shape(cdata.client_y, per_client),
                            ref_x=shape(cdata.ref_x), ref_y=shape(cdata.ref_y),
                            malicious=shape(cdata.malicious, per_client)),
                shape(jnp.zeros((), jnp.int32)))
    return step.lower(*args).compile().as_text()


def main(argv):
    if argv[:1] == ["--dump"]:
        root, name, out = argv[1:4]
        sys.path[:0] = [root, str(Path(root) / "src")]
        Path(out).write_text(normalise(compiled_step(name)))
        return 0
    other, cells = Path(argv[0]).resolve(), argv[1:] or CELLS
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
    differs = 0
    with tempfile.TemporaryDirectory() as tmp:
        for name in cells:
            texts = []
            for i, root in enumerate((ROOT, other)):
                out = Path(tmp) / f"{i}.{name}.txt"
                subprocess.run([sys.executable, __file__, "--dump", str(root),
                                name, str(out)], env=env, check=True)
                texts.append(out.read_text())
            same = texts[0] == texts[1]
            differs += not same
            print(f"{name}: {'same' if same else 'differs'}, "
                  f"{texts[0].count(chr(10))} / {texts[1].count(chr(10))} "
                  f"lines, sha256 "
                  f"{hashlib.sha256(texts[0].encode()).hexdigest()[:16]} / "
                  f"{hashlib.sha256(texts[1].encode()).hexdigest()[:16]}",
                  flush=True)
    return 1 if differs else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
