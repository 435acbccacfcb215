"""One-device (tp=1) replicas behind the router, on 4 host devices:
``Router.from_spec`` gives replica i device i, so each replica's weights
and KV cache sit on a device of their own (not all on device 0), and the
fleet's tokens match each replica's requests served alone."""
import numpy as np, jax, jax.numpy as jnp
from repro.models import ModelConfig, make_plan, init_params
from repro.inference.router import Router
from repro.inference.scheduler import make_trace
from repro.inference.spec import ReplicaSpec, ServeSpec, build_replica

N = 4
assert len(jax.devices()) == N, jax.devices()
cfg = ModelConfig(name="replica-tiny", family="dense", n_layers=2,
                  d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                  d_ff=128, vocab_size=96, dtype=jnp.float32)
ap = make_plan(cfg, 1)
params = init_params(jax.random.PRNGKey(0), ap)     # built on device 0
# arch is nominal: ap/params built from the tiny cfg are passed explicitly
RS = ReplicaSpec(arch="llama3.2-1b", slots=2, s_max=64, block_size=8,
                 admit_mode="chunked", admit_chunk=16)
fleet = Router.from_spec(ServeSpec(replica=RS, replicas=N), ap=ap,
                         params=params)


def devices_of(tree):
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


for i, rep in enumerate(fleet.replicas):
    want = {jax.devices()[i]}
    assert devices_of(rep.params) == want, (i, devices_of(rep.params))
    assert devices_of(rep.cache) == want, (i, devices_of(rep.cache))
print("replica placement OK")

reqs = make_trace(8, mean_in=10, mean_out=6, rate=4.0, vocab=cfg.vocab_size,
                  seed=2)


def copy(rs):
    return [r.__class__(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                        arrival_s=r.arrival_s) for r in rs]


done = {r.rid: r for r in fleet.run(copy(reqs))}
assert fleet.placements == [2] * N, fleet.placements
by_arrival = sorted(reqs, key=lambda r: r.arrival_s)
for i in range(N):
    solo = build_replica(RS, ap=ap, params=params,
                         devices=[jax.devices()[i]])
    sub = copy([r for k, r in enumerate(by_arrival) if k % N == i])
    for r in solo.run(sub):
        assert r.output is not None
        np.testing.assert_array_equal(done[r.rid].output, r.output)
print("replica devices OK")
