"""Shared by the MiMo tests: the plain reference (loaded from the
benchmark's file, which imports nothing from ``apex_tpu``), a toy
configuration with every mechanism of the real one, and a tap that
collects the logits of every position an engine run produced."""

import importlib.util
import os

import numpy as np

import jax
import jax.numpy as jnp

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "mimo_v2_reference",
    os.path.join(_HERE, os.pardir, "perf", "references", "mimo_v2.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

# window 8 > page 4; one dense global layer, five window layers, one
# global layer, all but the first with experts: the published order
TOY = dict(
    vocab_size=512, max_position_embeddings=4096,
    hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
    moe_layer_freq=(0, 1, 1, 1, 1, 1, 1),
    hidden_size=128, num_attention_heads=8, num_key_value_heads=1,
    swa_num_key_value_heads=2, head_dim=48, v_head_dim=32,
    intermediate_size=256, moe_intermediate_size=64, n_routed_experts=16,
    num_experts_per_tok=4, held_experts=(4, 4), sliding_window=8)


def toy_config(**changes):
    from apex_tpu.serving.mimo import MiMoConfig

    return MiMoConfig(**{**TOY, **changes})


def toy_params(cfg, seed=3, std=0.05):
    from apex_tpu.serving.mimo import init_params

    return init_params(cfg, seed, std=std)


class LogitsTap:
    """Wraps an engine's two jitted programs and keeps, for every
    (request id, position), the float32 logits row the engine computed
    there: ``rows[(rid, position)]`` predicts the token at ``position +
    1`` of that request's sequence."""

    def __init__(self, engine):
        self.engine, self.rows = engine, {}
        self._prefill, self._decode = engine._prefill_fn, engine._decode_fn
        engine._prefill_fn, engine._decode_fn = self.prefill, self.decode

    def _rid(self, slot):
        return self.engine.scheduler.slots[slot].request.rid

    def prefill(self, *args):
        out = self._prefill(*args)
        token_rows, gather = np.asarray(args[5]), np.asarray(args[7])
        positions = np.asarray(args[3])
        logits = np.asarray(out[1], np.float32)
        width = self.engine._gather_w
        for r in range(0, len(gather), width):
            slot = token_rows[gather[r]]
            if slot < self.engine.num_slots and (r == 0 or gather[r]):
                self.rows[(self._rid(slot), int(positions[gather[r]]))] = \
                    logits[r]
        return out

    def decode(self, *args):
        out = self._decode(*args)
        lengths = np.asarray(args[4])
        logits = np.asarray(out[2], np.float32)
        for slot, n in enumerate(lengths):
            if n > 0:
                self.rows[(self._rid(slot), int(n) - 1)] = logits[slot]
        return out


def serve(engine, requests, max_rounds=400):
    """Run ``requests`` to their end through ``engine.step``."""
    engine.step(arrivals=list(requests))
    for _ in range(max_rounds):
        if all(r.done() for r in requests):
            return
        engine.step()
    raise AssertionError("requests did not finish")


def compare(tap, requests, ref_logits_of):
    """Every tapped row against the reference's row at the same place:
    ``errors[n]`` is the largest absolute logit difference of one
    position, ``scale`` the largest reference logit in size."""
    errors, scale = [], 0.0
    for req in requests:
        seq = np.asarray(list(req.prompt) + list(req.out_tokens))
        ref = np.asarray(ref_logits_of(seq))
        scale = max(scale, float(np.abs(ref).max()))
        for pos in range(len(req.prompt) - 1, len(seq) - 1):
            errors.append(float(np.abs(
                tap.rows[(req.rid, pos)] - ref[pos]).max()))
    return np.asarray(errors), scale


def long_prompt_run(cfg, params, ref_logits_of):
    """A 300-token prompt, then a 20-token one, through an engine whose
    prefill packs 512 rows: the first dispatch's trunk runs on 512 rows
    (x top 4 = 2,048 assignment rows with 4 of 16 experts held: the
    expert layers' row bound, 1,024, engages), the second on 64 (256
    rows: it does not). Returns ``(errors against the reference, the two
    dispatches' prefill.fetch attributes)``."""
    import time

    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.scheduler import Request
    from apex_tpu.telemetry import spans

    engine = ServingEngine(cfg, params=params, num_slots=2, page_size=4,
                           num_pages=256, max_seq=512, prefill_len=512)
    tap = LogitsTap(engine)
    rs = np.random.RandomState(11)
    requests = [Request(rid=i, prompt=rs.randint(0, 512, n).tolist(),
                        max_new_tokens=3) for i, n in enumerate((300, 20))]
    t0 = time.perf_counter()
    for request in requests:
        serve(engine, [request])
    assert (tap._prefill._cache_size(), tap._decode._cache_size()) == (1, 1)
    fetches = [r.attrs for r in spans.snapshot(t0)
               if r.name == "prefill.fetch"]
    return compare(tap, requests, ref_logits_of)[0], fetches
