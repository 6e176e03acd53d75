from . import main
from ..utils.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    main()
