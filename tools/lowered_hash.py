"""Whether a change traces the program it traced before, without the chip.

Each named cell's train step (``hvd.make_train_step`` over the cell's job, at the cell's full size) is
LOWERED for a described ``v5e:2x2`` (not compiled: 3-11 s a cell on a CPU), the Mosaic payloads and the
``loc(...)`` annotations are masked, and the text is hashed.  Run it from the root of each tree that is to
be compared and compare the lines:

    cd <tree> && JAX_PLATFORMS=cpu python3 <repo>/tools/lowered_hash.py <cell> ...

prints ``<cell> <length of the text> <first 16 hex digits of its sha256>`` a cell.  Equal hashes: the two
trees hand XLA the same StableHLO, op for op and name for name (what XLA:TPU and Mosaic make of it is then
the same, so times and ``peak_hbm_gb`` are equal to the chip's own spread); a Mosaic kernel's BODY is
masked, so a change inside a kernel does not show here."""
import hashlib, os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P
import horovod_tpu.jax as hvd
from benchmark import manifest
import horovod_tpu.ops as ops_pkg, importlib, pkgutil
for m in pkgutil.iter_modules(ops_pkg.__path__):
    mod = importlib.import_module("horovod_tpu.ops." + m.name)
    if hasattr(mod, "_interpret"):
        mod._interpret = lambda: False
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
hvd.init()
for name in sys.argv[1:]:
    cell = manifest.cell(name)
    chips = cell["chips"]
    mesh = hvd.build_mesh(cell["traffic"]["mesh"], devices=topo.devices[:chips])
    job = manifest.load_job(cell["config"]["job"]).build(cell["config"], cell["traffic"], chips)
    rep = NamedSharding(mesh, P()); bat = NamedSharding(mesh, P(mesh.axis_names))
    state = jax.eval_shape(job.init_state, jax.random.key(0))
    batch = jax.eval_shape(job.make_batch, jax.random.key(0))
    sds = lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
    state = jax.tree.map(lambda x: sds(x, rep), state)
    step = hvd.make_train_step(job.loss_fn, job.optimizer, mesh, has_aux=job.has_aux)
    text = step.lower(*state, jax.tree.map(lambda x: sds(x, bat), batch)).as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = "..."', text)
    text = re.sub(r'loc\([^)]*\)', '', text)
    print(name, len(text), hashlib.sha256(text.encode()).hexdigest()[:16], flush=True)
