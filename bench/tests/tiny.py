"""A tiny cell for CPU tests: the same files' shapes at smoke widths."""

MODEL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
         "vocab_size": 256, "max_position_embeddings": 256,
         "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "qkv_bias": True,
         "tie_word_embeddings": False}

POLICY = {"method": "zipcache", "high_bits": 4, "low_bits": 2,
          "saliency_ratio": 0.4, "fp_window": 16, "recompress_interval": 16}

# Outputs long enough that no session finishes inside a test's window, as
# none does in the cells; every slot folds (token 17) before it opens.
TRAFFIC = {"clients": 4, "prompt_len": 128, "max_new": 128,
           "prompt_lengths": {"lo": 64, "hi": 128, "n": 2, "align": 64},
           "output_lengths": {"lo": 100, "hi": 120, "n": 2},
           "sessions_per_client": 2, "groups": [[0, 2], [1, 2]],
           "window_open_step": 14, "check_requests": 2}


# Limits at this size, from the program's readings on the CPU over seeds
# 1-8 (gap_max at most 0.211, gap_mean at most 0.0087): room above them,
# far below an altered token's gap (a logit spread of about one).
LIMITS = {"gap_max": 0.5, "gap_mean": 0.05}


def cell(limits=None):
    config = {"name": "tiny", "reference": "dense_gqa", "model": dict(MODEL),
              "policy": dict(POLICY),
              "program": {"serve_seed": 0, "argv": [
                  "--arch", "yi-6b", "--smoke", "--backend", "paged",
                  "--page-allocator", "freelist", "--paged-kernel", "on",
                  "--policy", "zipcache", "--saliency-ratio", "0.4",
                  "--seed", "0"]}}
    e2e = [{"name": n, "unit": u} for n, u in (
        ("tok_s", "tokens/s"), ("itl_p95_ms", "ms"), ("peak_hbm_gib", "GiB"),
        ("setup_s", "s"))]
    return {"name": "tiny", "entry": {"chips": 1}, "config": config,
            "traffic": dict(TRAFFIC),
            "limits": limits or LIMITS,
            "end_to_end": e2e, "per_layer": []}
