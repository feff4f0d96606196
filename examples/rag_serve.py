"""End-to-end serving driver (the paper's kind: filtered retrieval serving).

A SmolLM-135M-family encoder embeds documents and batched queries; the
fiber-navigable index answers metadata-filtered nearest-neighbour requests.

    PYTHONPATH=src python examples/rag_serve.py [--full]

--full uses the real smollm-135m config (slow on CPU); default is the
reduced same-family config so the example runs in seconds.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.search import SearchParams
from repro.core.types import Dataset, FilterPredicate
from repro.data.ground_truth import filtered_topk, recall_at_k
from repro.launch.mesh import make_mesh
from repro.models.transformer import ShardEnv, encode, init_params
from repro.serve.retrieval import EncodedRetriever, RetrievalService

ap = argparse.ArgumentParser()
ap.add_argument("--full", action="store_true")
ap.add_argument("--docs", type=int, default=2048)
ap.add_argument("--queries", type=int, default=32)
args = ap.parse_args()

cfg = get_config("smollm-135m") if args.full else reduced_config("smollm-135m")
env = ShardEnv(make_mesh((1, 1), ("data", "model")))
params = init_params(cfg, jax.random.PRNGKey(0))
enc = jax.jit(lambda p, b: encode(p, b, cfg, env))
rng = np.random.default_rng(0)

# --- offline: embed the document corpus, attach metadata, build the index --
t0 = time.time()
doc_tokens = rng.integers(0, cfg.vocab_size, (args.docs, 32)).astype(np.int32)
vecs = []
for s in range(0, args.docs, 256):
    vecs.append(np.asarray(enc(params, {"tokens": jnp.asarray(doc_tokens[s:s + 256])})))
vectors = np.concatenate(vecs)
meta = rng.integers(0, 8, (args.docs, 6)).astype(np.int32)
ds = Dataset(vectors, meta, [f"f{i}" for i in range(6)], [8] * 6)
service = RetrievalService.build(ds, graph_k=24, r_max=64,
                                 params=SearchParams(k=10))
print(f"indexed {args.docs} model-encoded docs in {time.time()-t0:.1f}s")

# --- online: batched filtered retrieval ------------------------------------
retr = EncodedRetriever(cfg, env, params, service)
q_tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (args.queries, 32)), jnp.int32)
pred = FilterPredicate.make({0: [2, 3], 3: [1, 4, 5]})
sel = pred.mask(meta).mean()
t0 = time.time()
out = retr.retrieve(q_tokens, pred)
dt = time.time() - t0
qvecs = retr.embed_tokens(q_tokens)
recs = []
for (ids, sims, stats), qv in zip(out, qvecs):
    gt, _ = filtered_topk(vectors, qv, pred.mask(meta), 10)
    recs.append(recall_at_k(np.asarray(ids), gt))
print(f"served {args.queries} filtered queries (selectivity {sel:.1%}) "
      f"in {dt*1000:.0f} ms ({dt*1000/args.queries:.1f} ms/q incl. encode)")
print(f"recall@10 vs exact filtered search: {np.mean(recs):.3f}")

# --- online, batched: all queries share each jitted restart round ----------
ids_b, _ = retr.retrieve_batch(q_tokens, [pred] * args.queries)  # compile
t0 = time.time()
ids_b, stats = retr.retrieve_batch(q_tokens, [pred] * args.queries)
dt_b = time.time() - t0
recs_b = [recall_at_k(np.asarray(ids), filtered_topk(
    vectors, qv, pred.mask(meta), 10)[0]) for ids, qv in zip(ids_b, qvecs)]
print(f"batched (device-resident atlas): {dt_b*1000:.0f} ms "
      f"({dt_b*1000/args.queries:.1f} ms/q incl. encode), "
      f"recall@10 {np.mean(recs_b):.3f}, "
      f"mean restarts {stats['walks'].mean():.2f}")
