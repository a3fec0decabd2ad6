from . import lint
