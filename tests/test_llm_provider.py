"""LLM provider tier tests: TPULLMProvider streaming, tool-call decoding,
pre-flight context errors, usage accounting, cancellation, and the
incremental detokenizer.

Runs a tiny random-init model on the CPU backend (conftest forces 8 virtual
devices); the ByteTokenizer makes text<->token behavior exact and cheap.
"""

import asyncio

import pytest

import jax

from kafka_tpu.core.types import ContextLengthError, Message
from kafka_tpu.llm import IncrementalDetokenizer, TPULLMProvider
from kafka_tpu.llm.base import LLMProvider
from kafka_tpu.llm.utils import count_images, infer_provider_from_model, prune_images
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.tokenizer import ByteTokenizer
from kafka_tpu.runtime import EngineConfig, InferenceEngine


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(scope="module")
def provider():
    tok = ByteTokenizer()
    cfg = ModelConfig(
        name="llm-test", vocab_size=tok.vocab_size, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, dtype="float32", max_context=2048,
    )
    params = init_params(cfg, jax.random.PRNGKey(11))
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=4, page_size=16, num_pages=128,
                     max_pages_per_seq=8, prefill_buckets=(16, 32, 64, 128)),
        kv_dtype=None,
    )
    p = TPULLMProvider(eng, tok, model_name="tiny-test")
    yield p
    run(p.aclose())


MESSAGES = [
    {"role": "system", "content": "You are a test model."},
    {"role": "user", "content": "Say something."},
]


class TestStreaming:
    def test_stream_shape(self, provider):
        async def go():
            chunks = []
            async for c in provider.stream_completion(
                MESSAGES, max_tokens=8, temperature=0.0
            ):
                chunks.append(c)
            return chunks

        chunks = run(go())
        # first chunk: role header; last: finish + usage
        assert chunks[0].role == "assistant"
        assert chunks[-1].finish_reason in ("stop", "length")
        assert chunks[-1].usage["completion_tokens"] >= 1
        assert chunks[-1].usage["prompt_tokens"] > 0
        # all chunks share one completion id
        assert len({c.id for c in chunks}) == 1

    def test_concurrent_streams_batch_together(self, provider):
        async def one(i):
            text = []
            async for c in provider.stream_completion(
                [{"role": "user", "content": f"prompt {i}"}],
                max_tokens=6, temperature=0.0,
            ):
                if c.content:
                    text.append(c.content)
            return "".join(text)

        async def go():
            return await asyncio.gather(*(one(i) for i in range(4)))

        outs = run(go())
        assert len(outs) == 4

    def test_completion_drains_stream(self, provider):
        resp = run(provider.completion(MESSAGES, max_tokens=6, temperature=0.0))
        assert resp.finish_reason in ("stop", "length")
        assert resp.usage["total_tokens"] > 0

    def test_deterministic_greedy(self, provider):
        r1 = run(provider.completion(MESSAGES, max_tokens=8, temperature=0.0))
        r2 = run(provider.completion(MESSAGES, max_tokens=8, temperature=0.0))
        assert r1.content == r2.content

    def test_context_length_preflight(self, provider):
        big = [{"role": "user", "content": "x" * 5000}]
        with pytest.raises(ContextLengthError) as ei:
            run(provider.completion(big))
        # error string must satisfy the reference-style classifier
        from kafka_tpu.llm.compaction import is_context_length_error

        assert is_context_length_error(ei.value)

    def test_validate_rejects_orphan_tool_message(self, provider):
        from kafka_tpu.core.types import LLMProviderError

        bad = [
            {"role": "user", "content": "hi"},
            {"role": "tool", "content": "res", "tool_call_id": "call_x"},
        ]
        with pytest.raises(LLMProviderError):
            run(provider.completion(bad))

    def test_image_parts_rejected_loudly(self, provider):
        """VERDICT r3 missing #1 decision: the text-only engine REJECTS
        image parts with a typed 400 instead of silently flattening them
        (reference forwarded them to multimodal models,
        src/llm/portkey.py:276)."""
        from kafka_tpu.core.types import UnsupportedContentError

        msgs = [{"role": "user", "content": [
            {"type": "text", "text": "what is this?"},
            {"type": "image_url", "image_url": {"url": "data:image/png;base64,x"}},
        ]}]
        with pytest.raises(UnsupportedContentError) as ei:
            run(provider.completion(msgs))
        assert ei.value.status_code == 400
        assert ei.value.n_parts == 1
        # text-only multi-part content still serves
        ok = [{"role": "user", "content": [{"type": "text", "text": "hi"}]}]
        resp = run(provider.completion(ok, max_tokens=2))
        assert resp.finish_reason in ("stop", "length")

    def test_cancellation_frees_engine(self, provider):
        async def go():
            agen = provider.stream_completion(
                [{"role": "user", "content": "long"}], max_tokens=400,
                temperature=0.0,
            )
            async for c in agen:
                if c.content:
                    break
            await agen.aclose()
            # give the worker a beat to process the cancel
            for _ in range(100):
                if provider.engine.num_active == 0 and not provider.engine.waiting:
                    break
                await asyncio.sleep(0.02)
            return provider.engine.num_active, len(provider.engine.waiting)

        active, waiting = run(go())
        assert active == 0 and waiting == 0

    def test_message_objects_accepted(self, provider):
        msgs = [Message(role="user", content="hello")]
        resp = run(provider.completion(msgs, max_tokens=4))
        assert resp.role == "assistant"


class TestIgnoreEos:
    """ServingConfig.ignore_eos (vLLM's): a sampled stop token does not end
    the reply, which runs to the `max_tokens` its caller asked for."""

    @pytest.mark.parametrize("ignore_eos, reason, n_tokens",
                             [(False, "stop", 3), (True, "length", 8)])
    def test_reply_length(self, provider, ignore_eos, reason, n_tokens):
        tok = provider.tokenizer
        script_ids = tok.encode("ab") + [tok.eot_id] + tok.encode("cdefg")

        def mask(output_ids):
            return [script_ids[len(output_ids)]]

        p = TPULLMProvider(provider.engine, tok, model_name="tiny-test",
                           worker=provider.worker, ignore_eos=ignore_eos)
        assert p.stop_token_ids == (() if ignore_eos else tuple(tok.stop_ids))
        resp = run(p.completion([{"role": "user", "content": "go"}],
                                max_tokens=8, temperature=0.0,
                                logits_mask_fn=mask))
        assert resp.finish_reason == reason
        assert resp.usage["completion_tokens"] == n_tokens
        # a stop token renders as nothing either way
        assert resp.content == ("ab" if not ignore_eos else "abcdefg")

    def test_no_environment_variable_only_the_key(self, monkeypatch):
        from kafka_tpu.server.config import ServingConfig

        monkeypatch.setenv("KAFKA_TPU_IGNORE_EOS", "1")
        assert ServingConfig.from_env().ignore_eos is False
        assert ServingConfig.from_env(ignore_eos=True).ignore_eos is True


class TestToolCallDecoding:
    def test_constrained_tool_call_stream(self, provider):
        """Force the model to emit a tool-call JSON via constrained decoding
        and check it surfaces as OpenAI tool_calls, not content."""
        tok = provider.tokenizer
        script = '{"name": "get_weather", "parameters": {"city": "Paris"}}'
        script_ids = tok.encode(script) + [tok.eot_id]

        def mask(output_ids):
            i = len(output_ids)
            return [script_ids[i]] if i < len(script_ids) else [tok.eot_id]

        async def go():
            chunks = []
            async for c in provider.stream_completion(
                [{"role": "user", "content": "weather?"}],
                max_tokens=len(script_ids) + 2,
                temperature=0.0,
                logits_mask_fn=mask,
            ):
                chunks.append(c)
            return chunks

        chunks = run(go())
        final = chunks[-1]
        assert final.finish_reason == "tool_calls"
        tc_chunks = [c for c in chunks if c.tool_calls]
        assert len(tc_chunks) == 1
        call = tc_chunks[0].tool_calls[0]
        assert call["function"]["name"] == "get_weather"
        assert '"Paris"' in call["function"]["arguments"]
        # no content chunks leaked while buffering
        assert not any(c.content for c in chunks)

    def test_plain_text_streams_incrementally(self, provider):
        tok = provider.tokenizer
        script = "hello world, this is streamed"
        script_ids = tok.encode(script) + [tok.eot_id]

        def mask(output_ids):
            i = len(output_ids)
            return [script_ids[i]] if i < len(script_ids) else [tok.eot_id]

        async def go():
            content_chunks = 0
            text = []
            async for c in provider.stream_completion(
                [{"role": "user", "content": "speak"}],
                max_tokens=len(script_ids) + 2, temperature=0.0,
                logits_mask_fn=mask,
            ):
                if c.content:
                    content_chunks += 1
                    text.append(c.content)
            return content_chunks, "".join(text)

        n, text = run(go())
        assert text == script
        assert n > 1  # streamed, not buffered into one chunk


class TestDetokenizer:
    def test_utf8_multibyte_held_back(self):
        tok = ByteTokenizer()
        detok = IncrementalDetokenizer(tok)
        ids = tok.encode("héllo ✓")
        out = []
        for t in ids:
            out.append(detok.push(t))
        out.append(detok.flush())
        assert "".join(out) == "héllo ✓"
        # no replacement characters ever emitted
        assert "�" not in "".join(out)

    def test_flush_emits_partial(self):
        tok = ByteTokenizer()
        detok = IncrementalDetokenizer(tok)
        ids = tok.encode("é")  # two bytes
        assert detok.push(ids[0]) == ""  # incomplete, held
        assert detok.push(ids[1]) == "é"
        assert detok.flush() == ""


class TestUtils:
    def test_provider_routing(self):
        assert infer_provider_from_model("gpt-4o") == "openai"
        assert infer_provider_from_model("claude-sonnet-4-5") == "anthropic"
        assert infer_provider_from_model("gemini-2.0-flash") == "google"
        assert infer_provider_from_model("llama-3.2-1b") == "tpu"

    def test_prune_images_keeps_newest(self):
        def img(i):
            return {"type": "image_url", "image_url": {"url": f"u{i}"}}

        msgs = [
            {"role": "user", "content": [img(0), {"type": "text", "text": "a"}]},
            {"role": "user", "content": [img(1), img(2)]},
        ]
        out = prune_images(msgs, max_images=1)
        assert count_images(out) == 1
        # the newest image survives
        assert out[1]["content"][1]["type"] == "image_url"
        # originals untouched
        assert count_images(msgs) == 3

    def test_prune_images_noop_under_cap(self):
        msgs = [{"role": "user", "content": "no images"}]
        assert prune_images(msgs, 19) is msgs


class TestModelInfo:
    def test_get_model_info(self, provider):
        info = provider.get_model_info()
        assert info["provider"] == "tpu"
        assert info["max_context"] == 2048
        assert info["supports_tools"]

    def test_available_models(self, provider):
        models = provider.get_available_models()
        assert models[0]["id"] == "tiny-test"

    def test_abc_contract(self):
        assert issubclass(TPULLMProvider, LLMProvider)
