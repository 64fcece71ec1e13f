"""chip_smoke.py's four-chip phase on four forced host devices. Run in a
subprocess by test_chip_smoke.py: the rest of the suite sees one device."""
import os
import sys
import tempfile
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402

import chip_smoke as smoke  # noqa: E402

assert len(jax.devices()) == 4, jax.devices()
with tempfile.TemporaryDirectory() as tmp:
    lengths = smoke.size_lengths(8 * 128, 1 << 20, seed=9)
    store = Path(tmp) / "store"
    index, _, _ = smoke.build_store(store, lengths, seed=9, workers=1,
                                    block_docs=128)   # eight shards
    assert index.storage.n_shards == 8, index.storage.n_shards
    smoke.phase_four_chips(store,
                           smoke.make_queries(lengths, 9, 24, seed=1),
                           smoke.Reference(index, 0.8), check_memory=False)
print("FOUR-CHIP-OK")
