"""The round's trace spans (``repro.core.spans``).

Every device scope names ops of the scanned chunk's compiled program, in
both client strategies, and the host spans of one chunk nest as
``scaffold.chunk`` > ``scaffold.dispatch`` then ``scaffold.fetch``.
"""
import glob
import re
from functools import partial

import jax
import pytest

from repro.configs import get_reduced
from repro.configs.base import FedRoundSpec
from repro.core import FederatedTrainer, spans
from repro.data.synthetic_lm import SyntheticLMFederated
from repro.models import model as M


def _trainer(strategy="client_sequential", arch="llama3.2-3b"):
    cfg = get_reduced(arch)
    spec = FedRoundSpec(algorithm="scaffold", num_clients=4, num_sampled=2,
                        local_steps=2, local_batch=1, eta_l=0.1,
                        strategy=strategy, update_space="lora", lora_rank=4)
    ds = SyntheticLMFederated(4, cfg.vocab_size, seq_len=16, seed=0)
    tr = FederatedTrainer(partial(M.loss_fn, cfg), partial(M.init_params, cfg),
                          spec, ds, seed=0, scan_rounds=1)
    assert tr.scan_active
    return tr


def _scopes_of(op_name):
    """The scope names of an ``op_name`` path, wrappers such as
    ``transpose(jvp(...))`` taken off."""
    return {re.sub(r"^(\w+\()+|\)+$", "", part) for part in op_name.split("/")}


@pytest.mark.parametrize("strategy", ["client_sequential", "client_parallel"])
def test_every_device_scope_names_ops_of_the_chunk(strategy):
    # a stack of Mamba-2 and attention layers with MoE tails carries them all
    tr = _trainer(strategy, "granite-4.0-h-small")
    text = tr._scan_fn.lower(
        tr.server, tr.device_store, tr._device_data, tr.device_sampler.key,
        tr._data_base_key, None, None, tr._device_sizes, 0, 1,
        tr.base_params).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    seen = set().union(*map(_scopes_of, names))
    assert set(spans.DEVICE_SCOPES) <= seen, sorted(set(spans.DEVICE_SCOPES) - seen)
    # backward ops carry the forward's scope inside the transpose wrapper
    assert any(f"transpose(jvp({spans.MODEL_BLOCKS}))" in n for n in names)


def test_chunk_holds_dispatch_then_fetch(tmp_path):
    from jax.profiler import ProfileData

    tr = _trainer()
    tr.run(1)  # compiled outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        tr.run(1)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events if e.name in spans.HOST_SPANS]
    by = {}
    for name, s, e, stats in host:
        by.setdefault(name, []).append((s, e, stats))
    assert {k: len(v) for k, v in by.items()} == {n: 1 for n in spans.HOST_SPANS}
    (c0, c1, args), = by[spans.CHUNK]
    (d0, d1, _), = by[spans.DISPATCH]
    (f0, f1, _), = by[spans.FETCH]
    assert c0 <= d0 < d1 <= f0 < f1 <= c1
    assert args == {"rounds": 1, "round": 1}
