from benchmark_tests import public_names

globals().update(public_names("test_kanana_files.py"))
