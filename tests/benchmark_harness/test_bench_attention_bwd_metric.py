from benchmark_tests import public_names

globals().update(public_names("test_attention_bwd_metric.py"))
