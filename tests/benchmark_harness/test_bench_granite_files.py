from benchmark_tests import public_names

globals().update(public_names("test_granite_files.py"))
