"""One module per architecture family.  A configuration names its
family (``"family": "benchmark.families.<name>"``); ``post_ln`` says
what a family module gives the harness."""
