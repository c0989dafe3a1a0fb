"""One module a model family, named by the ``"family"`` key of a
configuration's file and found by that name alone
(``lib/common.py:family_of``): the family's leaves, the program's model
built for serving or for training, the family's copy of the plain
reference and its work functions. ``gpt3.py`` lists the parts."""
